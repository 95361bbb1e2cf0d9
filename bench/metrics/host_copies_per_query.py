"""Device->host array copies per request completed in the measured
window: the engine's ``device_copies`` count (every array its blocking
reads copied: a batch's ids and dists, a tick's packed activity mask, a
retire's packed rows) over its completed queries (scheduler,
``serve/engine.py``). None where the engine has no such count."""


def read(record):
    stats = record["engine"]
    copies = stats.get("device_copies")
    if copies is None or not stats["queries"]:
        return None
    return copies / stats["queries"]
