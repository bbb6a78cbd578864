"""Host spans: profiler annotations that also keep in-memory totals.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` under ``name``, so
a running profiler records it on the host timeline beside the device's
operations, and adds the span's ``perf_counter_ns`` duration and one call
to a per-name total that ``totals()`` reads and ``reset()`` clears.  With
no profiler running a span costs about a microsecond on the host.

``step_span(name, step)`` is the same around one training step, as a
``jax.profiler.StepTraceAnnotation``: the profiler then groups every span
opened inside it under that step number.

The spans the program opens (``README.md``, "Tracing", lists them):
``train.*`` in ``repro.train.trainer.Trainer.fit``, ``dprime.densify`` and
``dprime.put`` in ``repro.core.signatures.densify_store``.  One tally
without a duration, ``count(name)``: ``pool_update.stripe_blocked`` and
``pool_update.gather_scatter``, one per pool leaf each time
``repro.optim.sparse`` traces its update, naming the path it took.
"""
from __future__ import annotations

import time

import jax

_TOTALS: dict[str, list[int]] = {}          # name -> [total ns, calls]


class span:
    """``with span(name) as s:`` -- ``s.t0`` / ``s.t1`` are the
    ``perf_counter_ns`` reads at entry and exit."""

    __slots__ = ("name", "t0", "t1", "_ann")

    def __init__(self, name: str, annotation=None):
        self.name = name
        self._ann = (jax.profiler.TraceAnnotation(name) if annotation is None
                     else annotation)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        tot = _TOTALS.get(self.name)
        if tot is None:
            _TOTALS[self.name] = [self.t1 - self.t0, 1]
        else:
            tot[0] += self.t1 - self.t0
            tot[1] += 1
        return False


def step_span(name: str, step: int) -> span:
    """A span that the profiler marks as step ``step``."""
    return span(name, jax.profiler.StepTraceAnnotation(name, step_num=step))


def count(name: str) -> None:
    """Tally one event under ``name``: a call with no duration."""
    tot = _TOTALS.setdefault(name, [0, 0])
    tot[1] += 1


def totals() -> dict[str, dict]:
    """{name: {"s": seconds in the span, "calls": times it closed}}."""
    return {k: {"s": ns / 1e9, "calls": n} for k, (ns, n) in _TOTALS.items()}


def reset() -> None:
    _TOTALS.clear()
