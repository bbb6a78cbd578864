"""From a profiler trace to device metrics: busy time, idle share, the top
device operations, and the idle gaps attributed to the host's spans.

``events`` flattens a trace into ``(plane, line, name, start_ns, dur_ns)``
rows; ``reduce`` works on such rows alone, so a few rows cut from a chip's
trace are enough to test it.

- Busy: the union of the intervals in which an operation ran on a device
  (a device plane's ``XLA Ops`` line), clipped to the window, averaged over
  the devices.  The window is the host span ``bench.window``.
- Device ops: each operation's time in the window less that of the ops
  nested in it, summed by its short name (instruction, shape, opcode).
- Idle gaps: the rest of the window, each gap charged to the ``bench.*``
  host span that overlaps it most (``no bench span`` when none does).
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SPAN = "no bench span"


def _planes(trace_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return ProfileData.from_file(paths[-1]).planes if paths else []


def layout(trace_dir: str) -> list[str]:
    """One line per plane and line of the trace, with its event count."""
    return [f"{plane.name} | {line.name} | {sum(1 for _ in line.events)}"
            for plane in _planes(trace_dir) for line in plane.lines]


def events(trace_dir: str) -> list[tuple]:
    """Rows of the newest ``.xplane.pb`` under ``trace_dir``: device op
    events and the host's ``bench.*`` spans."""
    rows = []
    for plane in _planes(trace_dir):
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if device or e.name.startswith("bench."):
                    rows.append((plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
    return rows


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def short_name(hlo: str) -> str:
    """``%fusion.2 = f32[6815744]{...} fusion(...)`` -> ``fusion.2 f32[6815744]
    fusion``: the instruction, its result shape and its opcode."""
    head, _, rest = hlo.partition(" = ")
    rest = re.sub(r"\{[^}]*\}", "", rest)
    shape = "tuple" if rest.startswith("(") else rest.split(" ")[0]
    op = re.search(r"([a-z][\w-]*)\(", rest)
    return " ".join(x for x in (head.lstrip("%"), rest and shape,
                                op and op.group(1)) if x)


def _self_times(evs, w0: int, w1: int) -> dict:
    """Each op's time inside the window less the time of the ops nested
    in it (a conditional or loop holds its body's ops), by short name."""
    out = collections.Counter()
    stack = []                                  # [name, start, end, child]
    for n, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        s, e = max(s, w0), min(e, w1)
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out[top[0]] += max(0, top[2] - top[1] - top[3])
        if e <= s:
            continue
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([short_name(n), s, e, 0])
    for top in stack:
        out[top[0]] += max(0, top[2] - top[1] - top[3])
    return out


def _charge(gaps: collections.Counter, a, b, spans, block: int = 4096):
    """Add each gap [a, b) to the span that overlaps it most; of spans
    that overlap it alike, the shortest (the innermost) takes it."""
    spans = sorted(spans, key=lambda x: x[2] - x[1])
    names = [n for n, _, _ in spans] + [NO_SPAN]
    s = np.asarray([x for _, x, _ in spans] + [0], np.int64)
    e = np.asarray([x for _, _, x in spans] + [0], np.int64)
    for lo in range(0, a.size, block):
        ga, gb = a[lo:lo + block, None], b[lo:lo + block, None]
        ov = np.minimum(e[None, :], gb) - np.maximum(s[None, :], ga)
        ov = np.where(ov > 0, ov, -1)
        ov[:, -1] = 0                       # NO_SPAN wins when nothing overlaps
        who = np.argmax(ov, axis=1)
        tot = np.bincount(who, weights=(gb - ga)[:, 0], minlength=len(names))
        for i in np.flatnonzero(tot):
            gaps[names[i]] += int(tot[i])


def reduce(rows: list[tuple], top: int = 10) -> dict | None:
    """{busy_s, window_s, idle_share, device_ops, idle_gaps}, or None when
    the rows hold no window or no device operation."""
    win = [(s, s + d) for p, _, n, s, d in rows
           if n == WINDOW and not p.startswith(DEVICE_PREFIX)]
    dev = collections.defaultdict(list)
    for p, _, n, s, d in rows:
        if p.startswith(DEVICE_PREFIX):
            dev[p].append((n, s, s + d))
    if not win or not dev:
        return None
    w0, w1 = win[0]
    busy_total, ops = 0.0, collections.Counter()
    gaps = collections.Counter()
    spans = [(n, s, s + d) for p, _, n, s, d in rows
             if n.startswith("bench.") and n != WINDOW
             and not p.startswith(DEVICE_PREFIX)]
    for plane, evs in dev.items():
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in evs
                   if e > w0 and s < w1]
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        for n, t in _self_times(evs, w0, w1).items():
            ops[n] += t
        edges = np.asarray([w0] + [x for iv in busy for x in iv] + [w1],
                           np.int64)
        a, b = edges[::2], edges[1::2]
        keep = b > a
        _charge(gaps, a[keep], b[keep], spans)
    n_dev = len(dev)
    window_s = (w1 - w0) / 1e9
    busy_s = busy_total / n_dev / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[n, v / n_dev / 1e9] for n, v in ops.most_common(top)
                           if v > 0],
            "idle_gaps": [[n, v / n_dev / 1e9]
                          for n, v in gaps.most_common(top)]}
