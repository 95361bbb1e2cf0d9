"""Share of the lane-rounds a flushed batch runs that serve a real query:
the real lanes' rounds over bucket size x the batch's slowest lane, in %.
The vmapped while-loop runs every lane, padding too, until the slowest one
ends (batch-flush dispatches only)."""
from bench.costs import real_lanes


def read(record):
    useful = run = 0
    for e, n in real_lanes(record["counters"]):
        if e["bucket"] is None:
            continue
        useful += int(e["rounds"][:n].sum())
        run += int(e["bucket"]) * int(e["rounds"].max())
    return 100.0 * useful / run if run else None
