"""Share of the HBM roofline that the compiled ``graph_search`` modules
reach: the bytes the window's queries need (``bench/costs.py``) over the
chip's HBM bandwidth (``bench/peaks.json``), over their device time, in %.
The traversal's arithmetic is a few operations per byte read, far below
the chip's ratio of compute to bandwidth, so bytes bound it."""
from bench.costs import module_seconds, window_bytes

MODULE = "jit_graph_search"


def read(record):
    seconds = module_seconds(record["trace"], MODULE)
    peaks = record["peaks"]
    if seconds <= 0 or not peaks:
        return None
    least = window_bytes(record["counters"], record["config"]) \
        / float(peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
