"""Traversal rounds per device dispatch of the continuous scheduler: the
engine's ``pool_rounds`` over its ``pool_dispatches`` in the measured
window (scheduler, ``serve/engine.py``). None where the engine has no such
count or dispatched nothing."""


def read(record):
    stats = record["engine"]
    dispatches = stats.get("pool_dispatches")
    if not dispatches:
        return None
    return stats["pool_rounds"] / dispatches
