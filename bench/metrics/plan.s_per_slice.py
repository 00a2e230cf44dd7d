"""Mean host-clock seconds of ``Segmenter.plan`` per slice in the window
(the benchmark's span around the program's own call)."""


def read(run):
    spans = [b - a for name, a, b in run.spans if name == "plan"]
    return sum(spans) / len(spans) if spans else None
