"""Mean MAP iterations per solved slice (the EM driver's ``map_iters``,
summed over its EM iterations): the solver's work as a count.  Reads
``em.map_iters_per_slice.serial`` too (the same quantity, split by the
end-to-end metric its cell reports)."""


def read(run):
    iters = [a.map_iters for a in run.answers]
    return sum(iters) / len(iters) if iters else None
