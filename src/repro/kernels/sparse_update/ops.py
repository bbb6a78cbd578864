"""Dispatch for the sparse optimizer update: Pallas on TPU, jnp elsewhere.

``sparse_update(algo, indices, values, states, **hyper)`` is the entry
point of the optimizers' gather/scatter pass (``repro/optim/sparse.py``;
Adagrad on a large bucketed stream streams the pool there instead).  On TPU the fused
Pallas gather -> moment-update -> scatter kernel runs compiled for BOTH
memory-pool layouts — flat [m] slabs (element-level records) and [rows, d]
slabs (row-mode SparseGrad: hashed_row / freq, including rowwise-Adam's
[rows] second moment) — so row schemes feed the kernel their native layout
with no flat-reshape round-trip.  Everywhere else the jnp reference is
already the optimal lowering (XLA's native gather/scatter), so unlike
the fused-embed engine there is no interpret-mode win to chase — interpret
mode here exists for kernel-parity tests only (pass ``interpret=True``).

Contract (shared with ``ref.py`` / ``kernel.py``): ``indices [K]`` sorted;
``unique=True`` (default) means sorted *unique* + sentinel-padded with the
slab's leading dim, values segment-summed with 0 at padded slots;
``unique=False`` means sorted-with-duplicates, no sentinels — the bucketed
striped-layout stream from ``optim/sparse.py::from_bucketed_locations`` —
and the kernel folds coincident slots in-pass (in-kernel dedup).  States
are touched only at live slots either way (add-of-delta scatters).
"""
from __future__ import annotations

import os

from repro.kernels import dispatch
from repro.kernels.sparse_update import kernel as _k
from repro.kernels.sparse_update import ref as _r

ALGOS = ("sgd", "adagrad", "adam")

# same VMEM budget knob as the fused embed engine: the no-grid kernel holds
# every state slab + the K vectors resident at once, so ALL of them must fit
_MAX_MEM_MB = int(os.environ.get("REPRO_FUSED_MAX_MEM_MB", "16"))
_TILE_RESERVE = 2 * 2**20


def _shapes_ok(algo: str, values, states) -> bool:
    """Kernel-supported layouts: flat [m] slabs with [K] values, or
    [rows, d] slabs with [K, d] values.  The ONLY state whose rank may drop
    below the values' is Adam's second moment (rowwise nu [rows] against
    [K, d] values) — any other 1-D-state/2-D-values mix routes to the jnp
    reference, which rejects it the same way the kernel would."""
    if values.ndim > 2:
        return False
    if algo == "adam" and len(states) == 2:
        return (states[0].ndim == values.ndim
                and states[1].ndim in (1, values.ndim))
    return all(s.ndim == values.ndim for s in states)


def _pallas_ok(algo, indices, values, states) -> bool:
    """TPU auto-dispatch gate: a supported slab layout, and the whole
    working set (all state slabs + index/value/update vectors) must fit the
    VMEM budget — an over-budget pool falls back to the jnp reference (XLA
    scatter), mirroring the fused engine's ``fused_supported`` gate.
    Explicit ``interpret=`` calls (kernel tests) bypass the size gate."""
    if not _shapes_ok(algo, values, states):
        return False
    resident = (sum(s.size * s.dtype.itemsize for s in states)
                + indices.size * 4 + 2 * values.size * values.dtype.itemsize)
    return resident + _TILE_RESERVE <= _MAX_MEM_MB * 2**20


def sparse_update(algo: str, indices, values, states: tuple, *,
                  unique: bool = True, interpret: bool | None = None,
                  **hyper):
    """-> (update_values [K, ...], new_states tuple).

    ``unique=False`` declares sorted-with-duplicates indices (bucketed
    layout) and turns on the in-kernel duplicate fold in whichever backend
    runs.  ``interpret=None``: Pallas (compiled) on TPU when eligible and
    the TPU dispatch rule (``repro.kernels.dispatch``) admits it, jnp ref
    elsewhere.  ``interpret=True`` forces the Pallas kernel in
    interpret mode (test hook); ``interpret=False`` forces compiled Pallas.
    """
    assert algo in ALGOS, algo
    use_pallas = (interpret is not None
                  and _shapes_ok(algo, values, states)) or (
        dispatch.platform() == "tpu"
        and dispatch.pallas_allowed("sparse_update")
        and _pallas_ok(algo, indices, values, states))
    if use_pallas and states:
        interp = bool(interpret)
        if algo == "sgd":
            return _k.sparse_sgd_pallas(indices, values, states[0],
                                        unique=unique, interpret=interp,
                                        **hyper)
        if algo == "adagrad":
            return _k.sparse_adagrad_pallas(indices, values, states[0],
                                            unique=unique, interpret=interp,
                                            **hyper)
        return _k.sparse_adam_pallas(indices, values, *states, unique=unique,
                                     interpret=interp, **hyper)
    if algo == "sgd":
        mo = states[0] if states else None
        return _r.sparse_sgd_ref(indices, values, mo, unique=unique, **hyper)
    if algo == "adagrad":
        return _r.sparse_adagrad_ref(indices, values, states[0],
                                     unique=unique, **hyper)
    return _r.sparse_adam_ref(indices, values, *states, unique=unique,
                              **hyper)
