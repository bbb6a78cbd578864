"""Non-finite step guard: skip a poisoned step without touching state.

``make_step`` builds the jitted train step shared by the Trainer and the
guard-overhead bench.  With ``guard=True`` the step checks, *in-jit*, that
the loss and every floating gradient leaf (dense arrays and ``SparseGrad``
values alike, including ``unique=False`` bucketed streams) are finite and
magnitude-bounded; a bad step selects the identity branch of a ``lax.cond``,
so params, opt_state and every optimizer moment come back bit-untouched —
the step is *skipped*, not clamped.  The caller reads the returned ``ok``
flag to count the skip (``health.skipped_steps``) and decide on rollback.

The magnitude bound (``max_abs_grad``) exists because overflow-scale
gradients (the ``huge_grad`` fault, 1e30) are finite: they pass an isfinite
check, then produce inf the moment the optimizer squares them.  Bounding
|g| catches the poison one step earlier, while the state is still clean.

The fault multiplier enters as a traced scalar argument: clean steps pass
1.0 (``x * 1.0`` is a bitwise identity for IEEE floats — including NaN
payloads — so guarded-but-unfaulted runs are bit-identical to never having
armed the injector), and the injector passes NaN/inf/1e30 to poison exactly
one step.
"""
from __future__ import annotations

import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.optim import sparse as sparse_lib
from repro.optim.optimizers import Optimizer, apply_updates

# Default gradient magnitude bound: generous enough that no real training
# signal trips it (f32 tops out ~3.4e38), tight enough that an overflow-bound
# gradient is caught before the optimizer squares it into inf.
MAX_ABS_GRAD = 1e18


def guard_enabled() -> bool:
    """``REPRO_GUARD_STEP`` gate (default on)."""
    return os.environ.get("REPRO_GUARD_STEP", "1").lower() not in (
        "0", "false", "off", "no")


def leaf_finite(x, max_abs: float | None = None) -> jax.Array | None:
    """Scalar bool for one gradient leaf; None for non-float leaves."""
    if sparse_lib.is_sparse(x):
        return x.all_finite(max_abs)
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return None
    ok = jnp.all(jnp.isfinite(x))
    if max_abs is not None:
        ok = ok & jnp.all(jnp.abs(x) <= max_abs)
    return ok


def all_finite(tree, max_abs: float | None = None) -> jax.Array:
    """Scalar bool: every floating leaf in ``tree`` is finite (and bounded).
    SparseGrad leaves are checked over their values."""
    checks = [c for c in (leaf_finite(x, max_abs) for x in
                          jax.tree_util.tree_leaves(
                              tree, is_leaf=sparse_lib.is_sparse))
              if c is not None]
    if not checks:
        return jnp.asarray(True)
    ok = checks[0]
    for c in checks[1:]:
        ok = ok & c
    return ok


def touched_indices(grads) -> jax.Array:
    """Concatenated slot indices of every ``SparseGrad`` leaf (sentinel-padded
    entries included — callers clip negatives).  This is the dirty-set feed
    for incremental checkpoints: exactly the slots this step's sparse update
    can write."""
    idx = [x.indices.reshape(-1)
           for x in jax.tree_util.tree_leaves(grads,
                                              is_leaf=sparse_lib.is_sparse)
           if sparse_lib.is_sparse(x)]
    if not idx:
        return jnp.zeros((0,), jnp.int32)
    return jnp.concatenate(idx)


def _scale_grads(grads, scale):
    """Multiply every floating gradient leaf (incl. SparseGrad values) by the
    traced fault scale; 1.0 is a bitwise no-op."""
    def one(x):
        if sparse_lib.is_sparse(x):
            return x.map_values(lambda v: v * scale)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return x * scale
        return x
    return jax.tree_util.tree_map(one, grads, is_leaf=sparse_lib.is_sparse)


def make_step(loss_fn: Callable, optimizer: Optimizer, *,
              sparse_grads: bool = False, guard: bool = True,
              donate: bool = True,
              max_abs_grad: float | None = MAX_ABS_GRAD,
              report_touched: bool = False):
    """Build the jitted train step.

    Its ops carry named scopes that a device trace groups by: the model's
    own (``lma_locations``, ``pool_gather``, ``table_lookup``,
    ``dense_net``), the sparse engine's (``record`` and ``provide`` around
    its two passes, ``sparse_grad``, ``pool_update``, ``table_update``) and
    the step's (``guard_check``, ``dense_update``).

    Returns ``step(params, opt_state, batch, fault_scale, *loss_args) ->
    (params, opt_state, loss, metrics, ok, grads_ok)`` where ``ok`` is the
    in-jit verdict (False -> the update was skipped and state is bit-identical
    to the input) and ``grads_ok`` distinguishes bad-gradient skips from
    bad-loss skips for the health counters.  With ``guard=False`` the step is
    the pre-guard fast path (no checks, no cond) and ``ok`` is constant True
    — the bench baseline for the overhead gate.

    ``report_touched=True`` appends a 7th output: the step's concatenated
    ``SparseGrad`` slot indices (``touched_indices``), which the trainer
    feeds to ``CheckpointManager.mark_dirty_slots`` for delta checkpoints.
    The indices are reported even for skipped steps; the trainer only marks
    them when ``ok``.

    ``loss_args`` reach ``loss_fn(params, batch, *loss_args)`` as jit
    arguments: device-resident inputs such as the D' signature buffers
    (GBs at Criteo width) must come in this way, since an array the step
    closed over would be embedded in the compiled program as a constant.
    """
    vg = (sparse_lib.sparse_value_and_grad(loss_fn) if sparse_grads
          else jax.value_and_grad(loss_fn, has_aux=True))
    true = jnp.asarray(True)

    def update(p, s, grads):
        # the dense leaves' update; pool leaves nest their own pool_update
        with jax.named_scope("dense_update"):
            updates, s = optimizer.update(grads, s, p)
            return apply_updates(p, updates), s

    def step(params, opt_state, batch, fault_scale, *loss_args):
        (loss, metrics), grads = vg(params, batch, *loss_args)
        with jax.named_scope("guard_check"):
            grads = _scale_grads(grads, fault_scale)
        touched = (touched_indices(grads),) if report_touched else ()
        if not guard:
            params, opt_state = update(params, opt_state, grads)
            return (params, opt_state, loss, metrics, true, true) + touched

        with jax.named_scope("guard_check"):
            grads_ok = all_finite(grads, max_abs_grad)
            ok = jnp.isfinite(loss) & grads_ok

        def apply(state):
            return update(*state, grads)

        params, opt_state = jax.lax.cond(
            ok, apply, lambda state: state, (params, opt_state))
        return (params, opt_state, loss, metrics, ok, grads_ok) + touched

    # donation intact: the skip branch is an identity, so donated buffers are
    # either updated in place or passed through untouched
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
