"""Milliseconds of the program's ``session.launch`` span per slice completed in
the traced window (``bench/spans.py``): the call into the EM executable
until it returns, which is asynchronous dispatch.  Reads
``session.launch.ms_per_slice.<variant>`` too (the same quantity, split by
the end-to-end metric its cell reports)."""

from bench import spans


def read(run):
    return spans.per_slice(run, "session.launch", scale=1e3)
