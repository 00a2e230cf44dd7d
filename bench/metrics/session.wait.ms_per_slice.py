"""Milliseconds of the program's ``session.wait`` span per slice completed in
the traced window (``bench/spans.py``): the wait for the EM result on the
device.  Reads ``session.wait.ms_per_slice.<variant>`` too (the same
quantity, split by the end-to-end metric its cell reports)."""

from bench import spans


def read(run):
    return spans.per_slice(run, "session.wait", scale=1e3)
