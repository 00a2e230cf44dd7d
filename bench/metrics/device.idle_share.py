"""Share of the traced window in which no operation ran on the device.
Reads ``device.idle_share.<cell kind>`` too: the quantity is split by the
end-to-end metric its cells report, not by how it is read."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
