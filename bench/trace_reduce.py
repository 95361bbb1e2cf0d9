"""Reduce a profiler trace of one traced window to device metrics.

:func:`from_xplane` reads the JAX profiler's ``*.xplane.pb`` into a plain
form, as small as the metrics need, which is also what the checked-in test
trace holds:

    {"planes": [
      {"name": "/device:TPU:0",
       "modules": [[module, start_ns, duration_ns], ...],   # XLA Modules
       "busy": [[start_ns, end_ns], ...],     # union of the XLA Ops line
       "op_self_s": {op: seconds}},           # self time per XLA op
      {"name": "/host:...", "spans": [[name, start_ns, duration_ns], ...]}]}

Host planes keep only the benchmark's own annotations (``bench.*``).
:func:`reduce_trace` then computes, over the window that the host span
``bench.window`` marks:

* ``busy_s``: seconds in which an operation ran on a device, averaged over
  the devices; ``window_s``: the window's length;
* ``module_s``: device seconds per compiled module, keyed by the module's
  name without its ``(id)`` suffix;
* ``device_ops``: the ten operations with the most self time (an op's time
  less that of the ops nested in it, as a while loop's body ops are);
* ``idle_gaps``: device idle time in the window, summed by the host span
  (``bench.*``) that overlaps each idle gap most, ``bench.poll`` where
  none does (the client polling the engine between logged spans).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
HOST_PREFIX = "bench."
POLL = "bench.poll"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _is_device(plane: dict) -> bool:
    return plane["name"].startswith("/device:")


def from_xplane(path: str) -> dict:
    """The plain form of the ``.xplane.pb`` at ``path`` (or the one under
    the directory ``path``)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[0]
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            out = {"name": plane.name, "modules": [], "busy": [],
                   "op_self_s": {}}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    out["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == OPS_LINE:
                    out["busy"], out["op_self_s"] = ops_summary(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
            planes.append(out)
        else:
            spans = [[e.name, e.start_ns, e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIX)]
            if spans:
                planes.append({"name": plane.name, "spans": spans})
    return {"planes": planes}


def ops_summary(events):
    """(busy intervals, self seconds per op) of one line of device ops,
    given as ``(name, start_ns, duration_ns)``. Ops nest (a while loop
    holds its body's ops); an op is named by its HLO instruction name."""
    evs = sorted((float(s), -float(s + d), name.split(" ", 1)[0])
                 for name, s, d in events)
    self_s = defaultdict(float)
    stack = []                                     # (end, name)
    for s, neg_end, name in evs:
        end = -neg_end
        # an op that ends after the open one is not nested in it
        while stack and (stack[-1][0] <= s or end > stack[-1][0]):
            stack.pop()
        if stack:
            self_s[stack[-1][1]] -= (end - s) / 1e9
        self_s[name] += (end - s) / 1e9
        stack.append((end, name))
    busy = union((s, -neg_end) for s, neg_end, _ in evs)
    return busy, dict(self_s)


def load(path: str) -> dict:
    """A trace in the plain form, from ``.json`` or ``.json.gz``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(trace: dict):
    for plane in trace["planes"]:
        for name, s, d in plane.get("spans", ()):
            if name == WINDOW:
                return s, s + d
    raise ValueError(f"no {WINDOW!r} span in the trace")


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_trace(trace: dict) -> dict:
    lo, hi = _window(trace)
    devices = [p for p in trace["planes"] if _is_device(p)]
    host = _HostSpans([(s, s + d, name) for p in trace["planes"]
                       for name, s, d in p.get("spans", ())
                       if name != WINDOW])
    busy_total = 0.0
    module_s = defaultdict(float)
    op_s = defaultdict(float)
    idle_by = defaultdict(float)
    for plane in devices:
        merged = union((max(s, lo), min(e, hi)) for s, e in plane["busy"]
                       if min(e, hi) > max(s, lo))
        busy_total += sum(e - s for s, e in merged) / 1e9
        for name, s, d in plane["modules"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                module_s[_module_name(name)] += (b - a) / 1e9
        for name, sec in plane["op_self_s"].items():
            op_s[name] += sec
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle_by[host.label(a, b)] += (b - a) / 1e9
    n = max(len(devices), 1)
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n,
        "devices": len(devices),
        "module_s": {k: v / n for k, v in module_s.items()},
        "device_ops": [[k, v / n] for k, v in top],
        "idle_gaps": [[k, v / n] for k, v in gaps],
    }


class _HostSpans:
    """The host's spans, for labelling device idle gaps. The client is one
    thread, so its spans do not overlap: sorted by start, the spans that
    meet an interval are consecutive."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        # a running maximum of the ends keeps the search right even if two
        # spans did overlap
        self.max_end, m = [], float("-inf")
        for _, e, _ in self.spans:
            m = max(m, e)
            self.max_end.append(m)

    def label(self, a: float, b: float) -> str:
        """The span that overlaps ``[a, b)`` most, or ``bench.poll``."""
        best, best_overlap = POLL, 0.0
        i = bisect.bisect_right(self.max_end, a)
        while i < len(self.spans) and self.spans[i][0] < b:
            s, e, name = self.spans[i]
            overlap = min(e, b) - max(s, a)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
            i += 1
        return best
