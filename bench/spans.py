"""The program's own spans and the traversal's named scopes, in a profiler
trace of a window.

The program's tracer (``repro.obs.Tracer``, on when the engine is opened
with ``ObsConfig(tracing=True)``) writes each of its spans into the
profiler's trace as a host annotation named ``repro.<span>``, and the
traversal's device programs carry ``jax.named_scope`` names in their HLO
``op_name`` metadata. A v5e's op events do not carry that metadata, so
each op is matched to the compiled programs' HLO text by its instruction
and result shape (:func:`hlo_scope_index`). :func:`from_xplane` reads both
into the plain form of ``bench/trace_reduce.py``, with the same keys and
two more:

    {"planes": [
      {"name": "/device:TPU:0", "modules": ..., "busy": ..., "op_self_s": ...,
       "ops": [[module, op, scope or None, self_s], ...]},
      {"name": "/host:...", "spans": [...],            # bench.*
       "program_spans": [[name, start_ns, duration_ns], ...]}]}

so ``trace_reduce.reduce_trace`` reads it as it reads the old form. Over
the window that ``bench.window`` marks:

* :func:`idle_by_span`: device idle seconds, split by the innermost program
  span covering each instant of an idle gap, ``(outside step)`` where none
  does;
* :func:`device_scopes`: device self seconds of the traversal's modules by
  the named scope of each op, ``(unscoped)`` where it has none.

The rest reads the tracer's own events (Chrome trace events, ``ts`` and
``dur`` in microseconds): per-step host time of the scheduler, host time of
the plan layer per device program it launched, and the longest steps.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace_reduce

PROGRAM_PREFIX = "repro."
# a v5e's trace also holds an empty "/device:CUSTOM:Megascale Trace" plane;
# counted as a device, it would halve every per-device average
CUSTOM_DEVICE = "/device:CUSTOM:"
OUTSIDE = "(outside step)"
UNSCOPED = "(unscoped)"
#: the compiled modules of the traversal (``core/search.py``)
TRAVERSAL_MODULES = ("jit_graph_search", "jit_graph_search_step",
                     "jit_init_search_state", "jit_finalize_search")
#: the traversal's ``jax.named_scope`` names
SCOPES = ("adt-build", "init-lanes", "loop-cond", "beam-select",
          "gather-adjacency", "gather-codes", "pq-lookup", "merge-sort",
          "gather-base", "rerank-exact", "topk-termination", "state-select",
          "final-rerank", "final-topk")
GATHER_SCOPES = ("gather-adjacency", "gather-codes", "gather-base")
# a scope is a whole component of the op_name path, bare or wrapped by a
# transformation: ".../vmap(gather-codes)/gather"
_SCOPE_RE = re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, SCOPES))
                       + r")(?=[/)]|$)")

#: the steps of the two schedulers (``serve/engine.py``)
STEPS = ("batch", "tick")
#: spans that launch a device program of the plan layer
LAUNCHES = ("dispatch", "init", "round", "finalize")
WAIT = "device-wait"


def scope_of(op_name: str):
    """The outermost traversal scope on an HLO ``op_name`` path, or None."""
    m = _SCOPE_RE.search(op_name or "")
    return m.group(1) if m else None


def _bare(op: str) -> str:
    return op.split(" ", 1)[0].lstrip("%")


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of a compiled module's HLO text. A fusion
    without metadata of its own takes that of the fused computation's
    instruction that names a scope (its root first)."""
    comp_names: dict = {}
    direct: dict = {}
    calls: dict = {}
    comp = None
    inst = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
    for line in hlo_text.splitlines():
        head = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and "=" not in line.split("{", 1)[0]:
            comp = head.group(1)
            continue
        m = inst.match(line)
        if not m:
            continue
        name = m.group(2)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name:
            direct[name] = op_name.group(1)
            if comp is not None:
                names = comp_names.setdefault(comp, [])
                names.insert(0 if m.group(1) else len(names),
                             op_name.group(1))
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if called:
            calls[name] = called.group(1)
    out = dict(direct)
    for name, comp in calls.items():
        if scope_of(out.get(name)) is None:
            scoped = [n for n in comp_names.get(comp, ()) if scope_of(n)]
            if scoped:
                out[name] = scoped[0]
    return out


def result_shape(instruction: str) -> str:
    """The result shape of one HLO instruction's text (``%x = SHAPE op(``),
    as the compiled module's text and the profiler's op events both print
    it; a tuple shape whole."""
    rhs = instruction.split(" = ", 1)[-1].lstrip()
    if not rhs.startswith("("):
        return rhs.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rhs):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rhs[:i + 1]
    return rhs


def hlo_scope_index(hlo_texts) -> dict:
    """``{(module, instruction, result shape): scope}`` over compiled
    modules' HLO texts, the key an op event of the device trace can be
    looked up by: its name says the instruction and its shape, and so
    which of a program's compiled batch shapes ran. Instructions without a
    scope are left out."""
    index: dict = {}
    for text in hlo_texts:
        head = re.search(r"^HloModule ([\w.\-]+)", text, re.M)
        if head is None:
            continue
        module = head.group(1)
        names = hlo_op_names(text)
        for line in text.splitlines():
            m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line)
            if not m:
                continue
            scope = scope_of(names.get(m.group(1)))
            if scope is not None:
                index.setdefault((module, m.group(1), result_shape(line)),
                                 scope)
    return index


def _module_at(modules, starts, t):
    """The raw name of the module running at ``t`` (with its id)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] + modules[i][2] >= t:
        return modules[i][0]
    return None


def scoped_ops(op_events, modules, hlo_index=None) -> list:
    """``[[module, op, scope, self_s], ...]`` of one device's ops, given as
    ``(name, start_ns, duration_ns)``. An op's scope comes from
    ``hlo_index`` (:func:`hlo_scope_index`): a v5e's op events carry no
    ``op_name`` among their stats, only the instruction and its shapes in
    their name. Self time as in ``trace_reduce.ops_summary``."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    hlo_index = hlo_index or {}
    known: dict = {}
    evs = []
    for name, s, d in op_events:
        raw = _module_at(mods, starts, float(s))
        key = (raw, name)
        if key not in known:
            module = None if raw is None else trace_reduce._module_name(raw)
            op = _bare(name)
            known[key] = (module, op,
                          hlo_index.get((module, op, result_shape(name))))
        evs.append((float(s), -float(s + d), known[key]))
    evs.sort(key=lambda e: (e[0], e[1]))
    self_s = defaultdict(float)
    stack = []
    for s, neg_end, key in evs:
        end = -neg_end
        while stack and (stack[-1][0] <= s or end > stack[-1][0]):
            stack.pop()
        if stack:
            self_s[stack[-1][1]] -= (end - s) / 1e9
        self_s[key] += (end - s) / 1e9
        stack.append((end, key))
    return [[m, op, scope, sec] for (m, op, scope), sec in self_s.items()]


def from_xplane(path: str, hlo_index=None, lo_ns=None, hi_ns=None) -> dict:
    """The plain form, with program spans and scoped ops, of the
    ``.xplane.pb`` at ``path`` (or under the directory ``path``), keeping
    only events that start in ``[lo_ns, hi_ns)`` when those are given."""
    import glob
    import os

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[0]
    lo = float("-inf") if lo_ns is None else lo_ns
    hi = float("inf") if hi_ns is None else hi_ns

    def keep(e):
        return lo <= e.start_ns < hi

    planes = []
    data = ProfileData.from_file(path)        # alive while its events are
    for plane in data.planes:
        if plane.name.startswith(CUSTOM_DEVICE):
            continue
        if plane.name.startswith("/device:"):
            out = {"name": plane.name, "modules": [], "busy": [],
                   "op_self_s": {}, "ops": []}
            ops = []
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    out["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events if keep(e)]
                elif line.name == trace_reduce.OPS_LINE:
                    ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events if keep(e)]
            out["busy"], out["op_self_s"] = trace_reduce.ops_summary(ops)
            out["ops"] = scoped_ops(ops, out["modules"], hlo_index)
            planes.append(out)
        else:
            spans, program = [], []
            for line in plane.lines:
                for e in line.events:
                    if not keep(e):
                        continue
                    if e.name.startswith(trace_reduce.HOST_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append([e.name[len(PROGRAM_PREFIX):],
                                        e.start_ns, e.duration_ns])
            if spans or program:
                planes.append({"name": plane.name, "spans": spans,
                               "program_spans": program})
    return {"planes": planes}


def segments(spans) -> list:
    """Disjoint ``(start, end, name, path)`` pieces of properly nested
    ``(start, end, name)`` spans, each labelled by the innermost span over
    it and that span's path of names (``tick/retire/fetch``)."""
    out = []
    stack = []                                   # (end, name, path)
    cursor = None

    def emit(a, b, name, path):
        if b > a:
            out.append((a, b, name, path))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, path = stack.pop()
            emit(cursor, end, nm, path)
            cursor = end
        if stack:
            emit(cursor, s, stack[-1][1], stack[-1][2])
            e = min(e, stack[-1][0])
        cursor = s
        path = f"{stack[-1][2]}/{name}" if stack else name
        stack.append((e, name, path))
    while stack:
        end, nm, path = stack.pop()
        emit(cursor, end, nm, path)
        cursor = end
    return out


def _idle(trace: dict, lo: float, hi: float) -> list:
    """Idle gaps ``[a, b)`` of each device in the window."""
    gaps = []
    for plane in trace["planes"]:
        if not trace_reduce._is_device(plane):
            continue
        merged = trace_reduce.union(
            (max(s, lo), min(e, hi)) for s, e in plane["busy"]
            if min(e, hi) > max(s, lo))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return gaps


def idle_by_span(trace: dict, by_path: bool = False) -> dict:
    """Device idle seconds in the window by the innermost program span over
    each instant of each gap (by its path with ``by_path``), averaged over
    the devices; ``(outside step)`` where no program span is open."""
    lo, hi = trace_reduce._window(trace)
    segs = segments([(s, s + d, name) for p in trace["planes"]
                     for name, s, d in p.get("program_spans", ())])
    starts = [s for s, _, _, _ in segs]
    n = max(sum(trace_reduce._is_device(p) for p in trace["planes"]), 1)
    out = defaultdict(float)
    for a, b in _idle(trace, lo, hi):
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            s, e, name, path = segs[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                out[path if by_path else name] += part
                covered += part
            i += 1
        out[OUTSIDE] += (b - a) - covered
    return {k: v / n / 1e9 for k, v in
            sorted(out.items(), key=lambda kv: -kv[1]) if v > 0}


def device_scopes(trace: dict) -> dict:
    """Device self seconds of the traversal modules' ops by named scope,
    averaged over the devices; ``(unscoped)`` for ops outside every
    scope. Empty when the trace has no scoped ops to read."""
    devices = [p for p in trace["planes"] if trace_reduce._is_device(p)]
    out = defaultdict(float)
    for plane in devices:
        for module, _, scope, sec in plane.get("ops", ()):
            if module in TRAVERSAL_MODULES:
                out[scope or UNSCOPED] += sec
    n = max(len(devices), 1)
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


# ---------------------------------------------------------------- tracer
def _span_tree(events):
    """The tracer's complete ("X") events as ``(span, children)`` trees,
    one per top-level span, in start order. Spans nest by time on the
    engine's one track."""
    spans = sorted((e for e in events if e.get("ph") == "X"),
                   key=lambda e: (e["ts"], -e["dur"]))
    roots, stack = [], []
    for e in spans:
        node = (e, [])
        while stack and stack[-1][0]["ts"] + stack[-1][0]["dur"] < e["ts"] \
                + e["dur"] - 1e-3:
            stack.pop()
        (stack[-1][1] if stack else roots).append(node)
        stack.append(node)
    return roots


def _self_us(node) -> float:
    e, children = node
    return e["dur"] - sum(c[0]["dur"] for c in children)


def _walk(node):
    yield node
    for c in node[1]:
        yield from _walk(c)


def steps(events) -> list:
    """The ``batch``/``tick`` trees that did work: a batch always, a tick
    when it holds a span."""
    out = []
    for root in _span_tree(events):
        for node in _walk(root):
            e, children = node
            if e["name"] in STEPS and (e["name"] == "batch" or children):
                out.append(node)
                break
    return out


def sched_host_ms_per_step(events):
    """Mean host self time of each step's scheduler spans (category
    ``serve``: the step and its engine-level children), per step that did
    work, in ms; None without steps."""
    done = steps(events)
    if not done:
        return None
    total = sum(_self_us(n) for root in done for n in _walk(root)
                if n[0].get("cat") == "serve")
    return total / len(done) / 1e3


def plan_host_ms_per_dispatch(events):
    """Host time of the plan layer's spans (category ``plan``; self time,
    ``device-wait`` left out) per device program it launched, in ms; None
    without a launch."""
    total, launches = 0.0, 0
    for root in _span_tree(events):
        for node in _walk(root):
            e = node[0]
            if e.get("cat") != "plan":
                continue
            if e["name"] in LAUNCHES:
                launches += 1
            if e["name"] != WAIT:
                total += _self_us(node)
    return total / launches / 1e3 if launches else None


def longest_steps(events, n: int = 3) -> list:
    """The ``n`` longest steps, each as ``(name, start_us, dur_us,
    [(path, dur_us), ...])`` over its spans below it."""
    def below(node, prefix):
        for c in node[1]:
            path = f"{prefix}/{c[0]['name']}"
            yield path, c[0]["dur"]
            yield from below(c, path)

    top = sorted(steps(events), key=lambda nd: -nd[0]["dur"])[:n]
    return [(e["name"], e["ts"], e["dur"], list(below((e, ch), e["name"])))
            for e, ch in top]


def gather_device_ms_per_query(scopes: dict, queries: int):
    """Device self time in the ``gather-*`` scopes per query served, ms."""
    if not scopes or not queries:
        return None
    return sum(scopes.get(s, 0.0) for s in GATHER_SCOPES) * 1e3 / queries
