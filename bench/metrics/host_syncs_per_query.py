"""Blocking device->host reads per request completed in the measured
window: the engine's ``device_syncs`` count (each read the scheduler or
the plan layer waits on: a batch's result fetch, a tick's ``active`` pull,
a retire's fetches) over its completed queries (scheduler,
``serve/engine.py``). None where the engine has no such count."""


def read(record):
    stats = record["engine"]
    syncs = stats.get("device_syncs")
    if syncs is None or not stats["queries"]:
        return None
    return syncs / stats["queries"]
