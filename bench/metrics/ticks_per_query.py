"""Scheduler ticks per retired request, from ``EngineStats`` (continuous
scheduler only)."""


def read(record):
    stats = record["engine"]
    if record["traffic"]["scheduler"] != "continuous" or not stats["retired"]:
        return None
    return stats["ticks"] / stats["retired"]
