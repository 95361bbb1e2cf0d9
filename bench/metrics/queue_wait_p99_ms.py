"""99th percentile of the engine's ``queue_wait_ms`` histogram: submit to
batch assembly or slot admission (scheduler, ``serve/engine.py``)."""


def read(record):
    h = record["queue_wait_ms"]
    if h is None or h.count == 0:
        return None
    return h.quantile(99.0)
