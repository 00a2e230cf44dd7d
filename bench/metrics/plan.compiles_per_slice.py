"""XLA compiles (JAX's ``/jax/core/compile/backend_compile_duration``
events) that end inside a ``plan`` span, per planned slice.  The window runs
with the persistent compilation cache off, so each is a real compile."""


def read(run):
    spans = [(a, b) for name, a, b in run.spans if name == "plan"]
    if not spans:
        return None
    n = sum(any(a <= t <= b for a, b in spans) for t, _ in run.compiles)
    return n / len(spans)
