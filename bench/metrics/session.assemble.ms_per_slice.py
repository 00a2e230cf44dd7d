"""Milliseconds of the program's ``session.assemble`` span per slice completed
in the traced window (``bench/spans.py``): the result's reads back to the
host and the region-to-pixel map.  Reads
``session.assemble.ms_per_slice.<variant>`` too (the same quantity, split by
the end-to-end metric its cell reports)."""

from bench import spans


def read(run):
    return spans.per_slice(run, "session.assemble", scale=1e3)
