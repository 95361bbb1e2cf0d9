"""Blocking device→host reads of the served path, through one helper.

Every read that makes the host wait for the device — the planner's result
fetch, a round session's ``active`` and ``rounds`` pulls, the engine's
retire fetch — goes through :meth:`DeviceSyncs.get`, which counts one
sync under its call site.  ``total`` always counts (one integer add); the
registry's ``device_syncs{site=...}`` only when metrics are on.

With the tracer on, the wait for the device (span ``device-wait``) and the
copy to the host (span ``fetch``) are timed apart.  That costs a second
host round trip per read (about 0.45 ms on a v5e), so with the tracer off
each array is copied straight away, its copy waiting for the device.
"""
from __future__ import annotations

import numpy as np

#: the call sites a sync is counted under
SYNC_SITES = frozenset({"execute", "active", "rounds", "retire"})


class DeviceSyncs:
    """One owner's count of blocking device→host reads (the planner owns
    one per opened target; its round sessions and the serving engine use
    it too)."""

    __slots__ = ("obs", "total")

    def __init__(self, obs):
        self.obs = obs
        self.total = 0

    def get(self, site: str, arrays):
        """``arrays`` (one device array, or a tuple or namedtuple of them)
        as host arrays, once the device has computed them.  ``site`` is one
        of ``SYNC_SITES``.  Each leaf is copied by itself, in order, as the
        read it replaces did: a pytree walk costs several times the copy of
        a small result."""
        self.total += 1
        obs = self.obs
        if obs.metrics.enabled:
            obs.metrics.counter("device_syncs", site=site)
        single = not isinstance(arrays, tuple)
        leaves = (arrays,) if single else arrays
        tracer = obs.tracer
        if tracer.enabled:
            with tracer.span("device-wait", cat="plan"):
                for a in leaves:
                    if hasattr(a, "block_until_ready"):
                        a.block_until_ready()
            with tracer.span("fetch", cat="plan"):
                out = [np.asarray(a) for a in leaves]
        else:
            out = [np.asarray(a) for a in leaves]
        if single:
            return out[0]
        return arrays._make(out) if hasattr(arrays, "_make") else tuple(out)
