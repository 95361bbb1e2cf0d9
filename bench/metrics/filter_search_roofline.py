"""Share of the HBM roofline that the filtered search's compiled modules
reach: the bytes the window's queries need (``bench/costs.py``, from each
dispatch's counters: the masked traversal's hops, PQ distances and exact
distances, and the scan's passing rows, ``n_pq``, and reranked rows,
``n_acc``) over the chip's HBM bandwidth (``bench/peaks.json``), over the
device time of the modules that serve them: ``jit_graph_search`` (masked
traversal) and ``jit__scan_kernel`` (the scan, ``filter/traversal.py``),
in %. The count is the same whatever implements the search. None without
those modules in the trace or without the peaks."""
from bench.costs import module_seconds, window_bytes

MODULES = ("jit_graph_search", "jit__scan_kernel")


def read(record):
    seconds = sum(module_seconds(record["trace"], m) for m in MODULES)
    peaks = record["peaks"]
    if seconds <= 0 or not peaks:
        return None
    least = window_bytes(record["counters"], record["config"]) \
        / float(peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
