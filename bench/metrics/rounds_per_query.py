"""Mean traversal rounds of the window's real queries, from the core
``SearchResult.rounds`` of each dispatch (``core/search.py``)."""
from bench.costs import real_lanes


def read(record):
    total = n_all = 0
    for e, n in real_lanes(record["counters"]):
        total += int(e["rounds"][:n].sum())
        n_all += n
    return total / n_all if n_all else None
