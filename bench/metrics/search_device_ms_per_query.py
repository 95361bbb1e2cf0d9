"""Device time of the compiled ``graph_search`` modules in the traced
window, per real query served by them (traversal on the device)."""
from bench.costs import module_seconds, window_queries

MODULE = "jit_graph_search"


def read(record):
    seconds = module_seconds(record["trace"], MODULE)
    queries = window_queries(record["counters"])
    if seconds <= 0 or not queries:
        return None
    return seconds * 1e3 / queries
