"""Mean seconds of the program's ``plan.hoods`` span per planned slice in
the traced window (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.per_plan(run, "plan.hoods")
