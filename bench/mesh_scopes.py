"""Device time of the table scopes on each chip of a mesh, from a profiler
trace's rows and the step's compiled HLO.

The program names the table lookup and the table update with the named
scopes ``table_lookup`` and ``table_update``; every op of a step carries
the scope in its ``op_name``.  A TPU's op events carry no ``op_name``, so
it comes from the compiled HLO text (``bench.scopes.hlo_op_names``).  An
op is a collective when its HLO opcode, or an opcode in the computation
it calls, is one of ``COLLECTIVES`` (async ``-start`` / ``-done`` halves
included).

Rows are ``bench.scopes.rows``': ``(plane, line, name, start_ns, dur_ns,
info)``.  ``reduce`` takes each device plane's ops inside the host span
``bench.window`` (self time, as ``bench.trace`` counts it) and sums them
by scope and by whether they are collectives; the window's steps are its
``train.step`` spans.  Readers take the chip that spent the most time in
a scope ("the slowest chip").
"""
from __future__ import annotations

import collections
import re

from bench import scopes, trace

SCOPES = ("table_lookup", "table_update")
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s*[ (].*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.-]+)")


def _is_collective(opcode: str) -> bool:
    return any(opcode.startswith(c) for c in COLLECTIVES)


def collective_instructions(hlo_text: str) -> set[str]:
    """Instructions that are collectives or call a computation holding
    one."""
    opcode, calls, comp_of = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = scopes._INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None and "=" not in line.split("{")[0]:
                comp = c.group(1)
            continue
        name = m.group(1)
        short = trace.short_name(line.strip().removeprefix("ROOT "))
        opcode[name] = short.rsplit(" ", 1)[-1]
        comp_of[name] = comp
        k = _CALLS.search(line)
        if k is not None:
            calls[name] = k.group(1)
    with_coll = {comp_of[n] for n, op in opcode.items() if _is_collective(op)}
    return {n for n, op in opcode.items()
            if _is_collective(op) or calls.get(n) in with_coll}


def scope_of(op_name: str) -> str | None:
    """The table scope in ``op_name``'s first path, if any."""
    comps = scopes._components(op_name.split(";")[0])
    return next((c for c in comps if c in SCOPES), None)


def reduce(rows: list[tuple], hlo_text: str) -> dict | None:
    """{"steps", "window_s", "chips": {plane: {"<scope>": s,
    "<scope>_exchange": s}}} over the ``bench.window`` span: seconds of
    device self time per chip, ``_exchange`` the collectives' share.
    None when the rows hold no window, no device op or no step."""
    win = [(s, s + d) for p, _, n, s, d, _ in rows
           if n == trace.WINDOW and not p.startswith(trace.DEVICE_PREFIX)]
    if not win:
        return None
    w0, w1 = win[0]
    steps = sum(1 for p, _, n, s, _, _ in rows
                if n == scopes.STEP and w0 <= s < w1
                and not p.startswith(trace.DEVICE_PREFIX))
    names = scopes.hlo_op_names(hlo_text)
    coll = collective_instructions(hlo_text)
    dev = collections.defaultdict(list)
    for p, line, n, s, d, info in rows:
        if p.startswith(trace.DEVICE_PREFIX) and line == trace.OPS_LINE:
            ins = scopes.instruction(n)
            scope = scope_of(info or names.get(ins, ("", False))[0])
            exch = ins in coll or _is_collective(ins)
            dev[p].append((f"{scope}|{int(exch)}", s, s + d))
    if not dev or not steps:
        return None
    chips = {}
    for plane, evs in sorted(dev.items()):
        out = {k: 0.0 for s in SCOPES for k in (s, s + "_exchange")}
        for key, t in trace._self_times(evs, w0, w1).items():
            scope, exch = key.split("|")
            if scope in SCOPES:
                out[scope] += t / 1e9
                if exch == "1":
                    out[scope + "_exchange"] += t / 1e9
        chips[plane] = out
    return {"steps": steps, "window_s": (w1 - w0) / 1e9, "chips": chips}


def slowest(r: dict | None, key: str) -> tuple[str, float] | None:
    """(plane, seconds per window step) of the chip with most ``key``."""
    if not r:
        return None
    plane = max(r["chips"], key=lambda p: r["chips"][p][key])
    return plane, r["chips"][plane][key] / r["steps"]


def exchange_s(r: dict, plane: str) -> float:
    """Seconds per window step of the collectives in both table scopes."""
    c = r["chips"][plane]
    return sum(c[s + "_exchange"] for s in SCOPES) / r["steps"]


def exchange_slowest(r: dict | None) -> tuple[str, float] | None:
    if not r:
        return None
    plane = max(r["chips"], key=lambda p: exchange_s(r, p))
    return plane, exchange_s(r, plane)


def roofline(ctx: dict, scope: str) -> float | None:
    """HBM share of a table scope on its slowest chip: the bytes that chip
    requires (``ctx["table_bytes"][scope]`` by plane) over the scope's
    time less its collectives', at the chip's HBM bandwidth, in %."""
    r = ctx.get("table_scopes")
    got = slowest(r, scope)
    if got is None:
        return None
    plane, s = got
    local = s - r["chips"][plane][scope + "_exchange"] / r["steps"]
    if local <= 0:
        return None
    return 100.0 * ctx["table_bytes"][scope][plane] / (
        local * ctx["peak"]["hbm_bytes_per_s"])
