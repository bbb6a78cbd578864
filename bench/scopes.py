"""From a profiler trace to device time by the program's named scope and
idle time by the program's host span, on one clock.

The program names its layers twice: ``jax.named_scope``s in the jitted step
land in each device op's ``op_name`` metadata, and ``repro.obs`` spans
(``train.*``) land on the host timeline.  This module reads both:

- Scope of a device op: the innermost of ``SCOPES`` in its ``op_name``
  path (``jvp(...)`` / ``transpose(...)`` wrappers are looked through), or
  ``other``.  A TPU's op events carry no ``op_name`` (their stats are the
  device offset and duration alone) and the trace's metadata plane reads
  empty, so the ``op_name`` comes from the step's compiled HLO text
  (instruction name -> ``metadata={op_name=...}``; a fusion takes its own
  metadata, and counts as spanning scopes when its fused ops carry more
  than one).
- Clock: host spans and device ops are on one timeline only up to an
  offset.  A step's run of the program (the device's ``XLA Modules``
  line) cannot start before its ``train.dispatch`` starts, and cannot end
  after its ``train.wait`` ends; each step bounds the offset from both
  sides.  The device intervals are shifted by the median of the steps'
  midpoints (inside the range every step allows); constraints that
  contradict each other read ``None`` rather than a guess.
- Idle: every instant of the window in which no op runs is charged to the
  innermost ``train.*`` span open at that instant (``no train span``
  when none is).

Rows are ``(plane, line, name, start_ns, dur_ns, info)``: ``info`` is a
device op's ``op_name`` when a row carries it (a recorded slice does), a
``train.step`` span's step number, or "".  ``rows`` reads them from a trace directory;
``reduce`` works on rows alone, so a slice cut from a chip's trace is
enough to test it.  ``bench.trace`` (busy share, top ops, ``bench.*`` gap
charge) is a separate reading of the same trace and is left as it is.
"""
from __future__ import annotations

import collections
import re
import statistics

import numpy as np

from bench import trace

SCOPES = ("lma_locations", "pool_gather", "dense_net", "sparse_grad",
          "pool_update", "dense_update", "guard_check")
PASSES = ("record", "provide")
OTHER = "other"
NO_SPAN = "no train span"
MODULES_LINE = "XLA Modules"
STEP = "train.step"
DISPATCH, WAIT, BATCH = "train.dispatch", "train.wait", "train.batch"

# per-layer metric -> scopes whose device self time it sums
DEV_METRICS = {"locations_dev_ms.train": ("lma_locations",),
               "gather_dev_ms.train": ("pool_gather",),
               "dense_dev_ms.train": ("dense_net",),
               "sparse_grad_dev_ms.train": ("sparse_grad",),
               "pool_update_dev_ms.train": ("pool_update",),
               "other_dev_ms.train": ("dense_update", "guard_check", OTHER)}

_WRAP = re.compile(r"^(?:[\w.-]+\()+|\)+$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s*[ (].*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.-]+)")


def _components(path: str) -> list[str]:
    return [_WRAP.sub("", c) for c in path.split("/")]


def scopes_of(op_name: str) -> set[str]:
    """The innermost scope of each path in ``op_name`` (XLA joins the
    names of merged ops with ``;``)."""
    out = set()
    for path in filter(None, op_name.split(";")):
        out.add(next((c for c in reversed(_components(path)) if c in SCOPES),
                     OTHER))
    return out or {OTHER}


def scope_of(op_name: str) -> str:
    """The innermost scope of ``op_name``'s first path, or ``other``."""
    first = op_name.split(";")[0]
    return next((c for c in reversed(_components(first)) if c in SCOPES),
                OTHER)


def pass_of(op_name: str) -> str | None:
    """``record`` or ``provide`` when the op belongs to one of the sparse
    gradient's two passes."""
    comps = _components(op_name.split(";")[0])
    return next((c for c in comps if c in PASSES), None)


def hlo_op_names(hlo_text: str) -> dict[str, tuple[str, bool]]:
    """Compiled HLO text -> {instruction: (op_name, spans two scopes)}.
    A fusion without metadata of its own takes its fused ops' first
    op_name; it spans scopes when its fused ops name more than one."""
    own, calls, comp_names = {}, {}, collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None and "=" not in line.split("{")[0]:
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        if op and comp is not None:
            comp_names[comp].append(op.group(1))
        k = _CALLS.search(line)
        if k is not None:
            calls[name] = k.group(1)
    out = {}
    for name, op in own.items():
        inner = comp_names.get(calls.get(name), [])
        scopes = set().union(*(scopes_of(x) for x in inner)) if inner else set()
        out[name] = (op or (inner[0] if inner else ""), len(scopes) > 1)
    return out


def instruction(event_name: str) -> str:
    """``%fusion.2 = f32[..] fusion(..)`` or ``fusion.2 f32[..] fusion`` ->
    ``fusion.2``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def rows(trace_dir: str) -> list[tuple]:
    """Rows of the newest ``.xplane.pb`` under ``trace_dir``: device ops,
    the device's module runs, and the host's ``train.*`` and ``bench.*``
    spans."""
    out = []
    for plane in trace._planes(trace_dir):
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (trace.OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith(("train.", "bench.")):
                    continue
                info = (str(dict(e.stats).get("step_num", ""))
                        if e.name == STEP else "")
                out.append((plane.name, line.name, e.name, int(e.start_ns),
                            int(e.duration_ns), info))
    return out


def clock_offset(steps: list[tuple[int, int]],
                 runs: list[tuple[int, int]]) -> dict | None:
    """The offset to add to device times to put them on the host's clock.

    ``steps``: each step's (``train.dispatch`` start, ``train.wait`` end)
    on the host; ``runs``: the (start, end) of each run of the step
    program on the device.  Each run is paired with the step it overlaps
    most; a paired run bounds the offset to ``[dispatch - start, wait -
    end]``.  Returns {offset_ns, lo_ns, hi_ns, n}: the median of the
    per-step midpoints, held inside ``[lo, hi]``, the range every step
    allows.  None when no run pairs with a step or the bounds contradict
    each other."""
    if not steps or not runs:
        return None
    s = np.asarray(steps, np.int64)
    lo, hi = [], []
    for a, b in runs:
        ov = np.minimum(s[:, 1], b) - np.maximum(s[:, 0], a)
        k = int(np.argmax(ov))
        if ov[k] <= 0:
            continue
        lo.append(int(s[k, 0]) - a)
        hi.append(int(s[k, 1]) - b)
    if not lo:
        return None
    l, h = max(lo), min(hi)
    if l > h:
        return None
    mid = statistics.median((x + y) / 2 for x, y in zip(lo, hi))
    return {"offset_ns": float(min(max(mid, l), h)), "lo_ns": l, "hi_ns": h,
            "n": len(lo)}


def innermost(spans: list[tuple[str, int, int]]) -> list[tuple[int, int, str]]:
    """Nested spans (one thread's) -> disjoint ``(start, end, name)``
    segments, each named for the innermost span open over it."""
    events = sorted(spans, key=lambda x: (x[1], -x[2]))
    out, stack, t = [], [], None

    def emit(upto):
        if stack and t is not None and upto > t:
            out.append((t, upto, stack[-1][0]))

    for name, a, b in events:
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            t = stack.pop()[1]
        emit(a)
        stack.append((name, b))
        t = a
    while stack:
        emit(stack[-1][1])
        t = stack.pop()[1]
    return out


def charge_idle(idle: list[tuple[int, int]],
                segments: list[tuple[int, int, str]]) -> collections.Counter:
    """Each idle instant to the segment open at it (``NO_SPAN`` outside
    every segment): both lists sorted and disjoint."""
    out = collections.Counter()
    j = 0
    for a, b in idle:
        covered = 0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        if b - a - covered:
            out[NO_SPAN] += b - a - covered
    return out


def _step_bounds(host) -> list[tuple[int, int]]:
    """(``train.dispatch`` start, end of the ``train.wait`` that follows
    it) of each step."""
    ends = sorted(e for n, _, e in host if n == WAIT)
    out = []
    for a in sorted(s for n, s, _ in host if n == DISPATCH):
        i = int(np.searchsorted(ends, a))
        if i < len(ends):
            out.append((a, ends[i]))
    return out


def reduce(rows: list[tuple], hlo_text: str | None = None) -> dict | None:
    """Device self time by scope and idle time by host span over the
    ``bench.window`` span, in seconds, with the clock offset used.  None
    when the rows hold no window, no device op or no step."""
    win = [(s, s + d) for p, _, n, s, d, _ in rows
           if n == trace.WINDOW and not p.startswith(trace.DEVICE_PREFIX)]
    dev = collections.defaultdict(list)
    mods = collections.defaultdict(list)
    for p, line, n, s, d, info in rows:
        if not p.startswith(trace.DEVICE_PREFIX):
            continue
        if line == MODULES_LINE:
            mods[p].append((s, s + d))
        else:
            dev[p].append((n, s, s + d, info))
    host = [(n, s, s + d) for p, _, n, s, d, _ in rows
            if not p.startswith(trace.DEVICE_PREFIX) and n.startswith("train.")]
    if not win or not dev:
        return None
    w0, w1 = win[0]
    n_steps = sum(1 for n, s, _ in host if n == STEP and w0 <= s < w1)
    if not n_steps:
        return None
    by_hlo = hlo_op_names(hlo_text) if hlo_text else {}
    segs = innermost(host)
    steps = _step_bounds(host)
    by_scope, by_pass = collections.Counter(), collections.Counter()
    spanning, idle, busy_total = 0, collections.Counter(), 0
    offsets, source = [], collections.Counter()
    for plane, evs in dev.items():
        named = []
        for n, s, e, info in evs:
            op, two = info, False
            if op:
                source["row"] += 1
            elif instruction(n) in by_hlo:
                op, two = by_hlo[instruction(n)]
                source["hlo"] += 1
            named.append((n, s, e, op, two))
        off = clock_offset(steps, mods.get(plane, []))
        offsets.append(off)
        # self time as bench.trace counts it, keyed by pass|scope|spanning
        keyed = [(f"{pass_of(op) or '-'}|{scope_of(op)}|{int(two)}", s, e)
                 for _, s, e, op, two in named]
        for key, t in trace._self_times(keyed, w0, w1).items():
            p, scope, two = key.split("|")
            by_scope[scope] += t
            by_pass[(p, scope)] += t
            spanning += t if two == "1" else 0
        if off is None:
            continue
        d = int(round(off["offset_ns"]))
        busy = trace._union([(max(s + d, w0), min(e + d, w1))
                             for _, s, e, _, _ in named
                             if e + d > w0 and s + d < w1])
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        idle.update(charge_idle(gaps, segs))
    n_dev = len(dev)
    dev_s = {k: v / n_dev / 1e9 for k, v in by_scope.items()}
    busy_s = sum(dev_s.values())
    out = {"window_s": (w1 - w0) / 1e9, "steps": n_steps,
           "scope_s": dev_s,
           "pass_scope_s": {f"{p}/{s}": v / n_dev / 1e9
                            for (p, s), v in sorted(by_pass.items())},
           "spanning_share": spanning / n_dev / 1e9 / busy_s if busy_s else 0.0,
           "op_name_source": dict(source),
           "clock": None, "idle_s": None}
    if any(o is None for o in offsets):
        return out
    out["clock"] = {f"{k}_us": [o[f"{k}_ns"] / 1e3 for o in offsets]
                    for k in ("offset", "lo", "hi")}
    out["clock"]["steps"] = [o["n"] for o in offsets]
    out["idle_s"] = {k: v / n_dev / 1e9 for k, v in idle.items()}
    out["shifted_busy_s"] = busy_total / n_dev / 1e9
    return out


def metrics(r: dict | None) -> dict:
    """The per-layer metrics of a reduction, in ms per window step (idle
    metrics left out when the clock could not be set)."""
    if r is None:
        return {}
    k = 1e3 / r["steps"]
    out = {m: sum(r["scope_s"].get(s, 0.0) for s in scopes) * k
           for m, scopes in DEV_METRICS.items()}
    if r["idle_s"] is not None:
        batch = r["idle_s"].get(BATCH, 0.0)
        out["idle_batch_ms.train"] = batch * k
        out["idle_host_ms.train"] = (sum(r["idle_s"].values()) - batch) * k
    return out
