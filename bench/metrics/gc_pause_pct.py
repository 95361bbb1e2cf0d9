"""Share of the measured window that Python's garbage collector held the
single-threaded serving loop, in % (host runtime under ``serve/engine.py``;
host clock)."""


def read(record):
    return 100.0 * record["gc_pause_s"] / record["log"].seconds
