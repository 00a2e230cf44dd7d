"""Seconds of the program's ``session.pad`` span (padding a plan into its
bucket, with the compile of ``pad_hoods`` for a fresh slice's shape) per
slice completed in the traced window (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.per_slice(run, "session.pad")
