"""Mean solver iterations over the window's solves, as the program counts
them (``CGInfo.iters``, or the PageRank loop's count)."""

import statistics


def read(record):
    iters = record.counters.get("iters")
    return statistics.fmean(iters) if iters else None
