"""XLA compile seconds that ran in the program's ``plan`` span or one of its
stages, per planned slice in the traced window (``bench/spans.py``).  The
window runs with the persistent compilation cache off, so each is a real
compile."""

from bench import spans


def read(run):
    return spans.plan_compile_s(run)
