"""Optimizers (optax is not installed — this is the substrate).

API mirrors optax: ``opt.init(params) -> state``; ``opt.update(grads, state,
params) -> (updates, state)``; ``apply_updates(params, updates)``.  DLRM-style
models traditionally use SGD/Adagrad for embeddings (sparse-friendly: Adagrad's
accumulator is elementwise, exactly right for LMA's shared memory M where rows
are aliased) and Adam(W) for dense towers; ``multi_transform`` routes by path.

Gradient trees may carry :class:`repro.optim.sparse.SparseGrad` leaves (the
deduped sparse gradient of a memory pool).  Every transform here routes them:
``sgd`` / ``adagrad`` / ``adam`` delegate such leaves to the lazy sparse
kernel (one O(K) gather -> moment-update -> scatter instead of the O(m)
dense pass — exactly the dense update for Adagrad and momentum-less SGD),
``scale`` / ``clip_by_global_norm`` map over the values, ``multi_transform``
treats them as leaves when routing by path, and ``apply_updates`` applies
them as an O(K) scatter-add.  Adagrad on a striped pool's bucketed stream
may instead stream the pool and return its new value as a
``repro.optim.sparse.NewValue``, which ``apply_updates`` takes as it is and
any later transform refuses.  Dense leaves are bit-unchanged.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (grads, state, params) -> (updates, state)


def _is_sparse(x) -> bool:
    from repro.optim.sparse import SparseGrad
    return isinstance(x, SparseGrad)


def _is_new_value(x) -> bool:
    from repro.optim.sparse import NewValue
    return isinstance(x, NewValue)


def _is_leaf(x) -> bool:
    return _is_sparse(x) or _is_new_value(x)


def _no_new_value(x):
    if _is_new_value(x):
        raise TypeError(
            "a transform after the stripe-blocked Adagrad: its update is "
            "already the pool's new value and cannot be transformed; put "
            "the transform before the optimizer in the chain")
    return x


def _gmap(fn, grads, *rest):
    """tree_map over a gradient tree with SparseGrad leaves kept opaque;
    ``fn`` on a sparse leaf maps its values (indices untouched).  A
    ``NewValue`` leaf raises."""
    def one(g, *r):
        if _is_sparse(_no_new_value(g)):
            return g.map_values(lambda v: fn(v, *r))
        return fn(g, *r)
    return jax.tree_util.tree_map(one, grads, *rest, is_leaf=_is_leaf)


class _Pair:
    """Opaque (update, state) holder — unregistered, so tree_flatten treats
    it as a leaf regardless of what containers the param tree uses."""
    __slots__ = ("u", "s")

    def __init__(self, u, s):
        self.u, self.s = u, s


def _split_pairs(out):
    """Tree of _Pair leaves -> (updates tree, states tree)."""
    flat, td = jax.tree_util.tree_flatten(
        out, is_leaf=lambda x: isinstance(x, _Pair))
    return (jax.tree_util.tree_unflatten(td, [o.u for o in flat]),
            jax.tree_util.tree_unflatten(td, [o.s for o in flat]))


def apply_updates(params, updates):
    from repro.optim import sparse as sp

    def one(u, p):
        if _is_sparse(u):
            return sp.sparse_apply(p, u)
        if _is_new_value(u):
            return u.value
        return (p + u).astype(p.dtype)

    return jax.tree_util.tree_map(one, updates, params, is_leaf=_is_leaf)


# ------------------------------------------------------------------ transforms

def scale(factor: float) -> Optimizer:
    return Optimizer(
        init=lambda params: (),
        update=lambda g, s, p=None: (_gmap(lambda x: x * factor, g), s),
    )


def scale_by_schedule(schedule: Callable[[jax.Array], jax.Array]) -> Optimizer:
    def init(params):
        return jnp.zeros((), jnp.int32)

    def update(g, step, p=None):
        lr = schedule(step)
        return _gmap(lambda x: x * lr, g), step + 1

    return Optimizer(init, update)


def clip_by_global_norm(max_norm: float) -> Optimizer:
    def update(g, s, p=None):
        # SparseGrad values are deduped (segment-summed), so their square-sum
        # equals the dense leaf's square-sum exactly
        leaves = jax.tree_util.tree_leaves(g, is_leaf=_is_leaf)
        vals = [x.values if _is_sparse(x) else _no_new_value(x)
                for x in leaves]
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in vals))
        factor = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
        return _gmap(lambda x: x * factor, g), s

    return Optimizer(lambda p: (), update)


def sgd(lr: float, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def update(g, s, p=None):
        if momentum == 0.0:
            return _gmap(lambda x: -lr * x, g), s
        from repro.optim.sparse import sgd_leaf
        return _split_pairs(jax.tree_util.tree_map(
            lambda x, m: _Pair(*sgd_leaf(x, m, lr=lr, momentum=momentum)),
            g, s, is_leaf=_is_sparse))

    return Optimizer(init, update)


def adagrad(lr: float, eps: float = 1e-10, initial_acc: float = 0.0) -> Optimizer:
    def init(params):
        return jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, initial_acc, dtype=jnp.float32), params)

    def update(g, acc, p=None):
        from repro.optim.sparse import adagrad_tree
        return adagrad_tree(g, acc, p, lr=lr, eps=eps)

    return Optimizer(init, update)


def _map_leading(fn, args, threshold_bytes: int = 1 << 27):
    """Apply a per-leaf optimizer update layer-by-layer (lax.map over the
    stacked leading axis) when the leaf is large.

    Stacked-layer parameters ([L, ...] from scanned transformer blocks) would
    otherwise materialize several f32 temporaries of the WHOLE stack during
    the update — 3.2 GiB each for DeepSeek-V3's [58, E, 7168, 2048] experts,
    ~25 GiB of optimizer scratch per device.  Mapping over layers bounds the
    scratch to one layer (55 MB).  Per-layer second-moment clipping is also
    the semantically right unit: each layer is a separate parameter tensor
    that only happens to be stored stacked.
    """
    x = args[0]
    if x.ndim >= 3 and x.shape[0] > 1 and x.size * 4 > threshold_bytes:
        return jax.lax.map(lambda a: fn(*a), args)
    return fn(*args)


class AdafactorState(NamedTuple):
    step: jax.Array
    vs: object  # pytree: per-leaf dict {"v_row","v_col"} (factored) or {"v"}


def adafactor(lr: float, decay_exp: float = 0.8, eps: float = 1e-30,
              clip_threshold: float = 1.0, min_factor_dim: int = 128) -> Optimizer:
    """Adafactor (Shazeer & Stern 2018), the memory lever for 100B+ training:
    the second moment of an [..., n, m] matrix is stored as row/col means —
    O(n+m) f32 instead of O(n*m) (671B params: ~25 MB vs 10.5 GiB/device)."""

    def _factored(shape):
        return (len(shape) >= 2 and shape[-1] >= min_factor_dim
                and shape[-2] >= min_factor_dim)

    def init(params):
        def one(x):
            if _factored(x.shape):
                return {"v_row": jnp.zeros(x.shape[:-1], jnp.float32),
                        "v_col": jnp.zeros(x.shape[:-2] + x.shape[-1:],
                                           jnp.float32)}
            return {"v": jnp.zeros(x.shape, jnp.float32)}
        return AdafactorState(jnp.zeros((), jnp.int32),
                              jax.tree_util.tree_map(one, params))

    def update(grads, state, params=None):
        step = state.step + 1
        beta2 = 1.0 - step.astype(jnp.float32) ** (-decay_exp)

        def one(g, v):
            gf = g.astype(jnp.float32)
            g2 = jnp.square(gf) + eps
            if "v_row" in v:
                v_row = beta2 * v["v_row"] + (1 - beta2) * jnp.mean(g2, axis=-1)
                v_col = beta2 * v["v_col"] + (1 - beta2) * jnp.mean(g2, axis=-2)
                row_mean = jnp.mean(v_row, axis=-1, keepdims=True)
                vhat = (v_row / jnp.maximum(row_mean, eps))[..., None] \
                    * v_col[..., None, :]
                new_v = {"v_row": v_row, "v_col": v_col}
            else:
                vhat = beta2 * v["v"] + (1 - beta2) * g2
                new_v = {"v": vhat}
            u = gf * jax.lax.rsqrt(vhat + eps)
            rms_u = jnp.sqrt(jnp.mean(jnp.square(u)) + eps)
            u = u / jnp.maximum(1.0, rms_u / clip_threshold)
            # scale + cast INSIDE the (layer-mapped) body: the stacked update
            # leaves the map at param width, never as an f32 stack
            return (-lr * u).astype(g.dtype), new_v

        # adafactor has no lazy-sparse form (the factored second moment is
        # global by construction); densify sparse leaves — correct, O(m).
        # A row-mode SparseGrad densifies to its (rows, d) view; reshape it
        # back to the flat param/state layout the moments were built from.
        def _densify_like(g, v):
            d = g.densify()
            ref = v.get("v")
            return d.reshape(ref.shape) if ref is not None \
                and d.shape != ref.shape else d

        leaves, treedef = jax.tree_util.tree_flatten(grads, is_leaf=_is_sparse)
        vleaves = treedef.flatten_up_to(state.vs)
        leaves = [_densify_like(g, v) if _is_sparse(g) else g
                  for g, v in zip(leaves, vleaves)]
        outs = [_map_leading(one, (g, v)) for g, v in zip(leaves, vleaves)]
        updates = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
        new_vs = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
        return updates, AdafactorState(step, new_vs)

    return Optimizer(init, update)


class AdamState(NamedTuple):
    step: jax.Array
    mu: object
    nu: object


def adam(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        z = lambda: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        return AdamState(jnp.zeros((), jnp.int32), z(), z())

    def update(g, state, params=None):
        step = state.step + 1
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def one(x, m, n, p):
            """Fused per-leaf moment update + step (layer-mapped when big)."""
            xf = x.astype(jnp.float32)
            m = b1 * m + (1 - b1) * xf
            n = b2 * n + (1 - b2) * jnp.square(xf)
            u = -lr * (m / bc1) / (jnp.sqrt(n / bc2) + eps)
            if weight_decay:
                u = u - lr * weight_decay * p.astype(jnp.float32)
            return u.astype(x.dtype), m, n

        def leaf(x, m, n, p):
            if _is_sparse(x):
                # lazy (SparseAdam) semantics on sparse pool grads: O(K)
                # moment update + lazy decoupled decay, untouched slots
                # keep stale moments
                from repro.optim.sparse import adam_leaf
                return adam_leaf(x, m, n, p if not _is_sparse(p) else None,
                                 lr=lr, b1=b1, b2=b2, bc1=bc1, bc2=bc2,
                                 eps=eps, weight_decay=weight_decay)
            return _map_leading(one, (x, m, n, p))

        leaves, treedef = jax.tree_util.tree_flatten(g, is_leaf=_is_sparse)
        ms = treedef.flatten_up_to(state.mu)
        ns = treedef.flatten_up_to(state.nu)
        ps = (treedef.flatten_up_to(params) if params is not None else leaves)
        outs = [leaf(x, m, n, p) for x, m, n, p in zip(leaves, ms, ns, ps)]
        unf = lambda i: jax.tree_util.tree_unflatten(
            treedef, [o[i] for o in outs])
        return unf(0), AdamState(step, unf(1), unf(2))

    return Optimizer(init, update)


def adamw(lr: float, weight_decay: float = 0.01, **kw) -> Optimizer:
    return adam(lr, weight_decay=weight_decay, **kw)


def chain(*transforms: Optimizer) -> Optimizer:
    def init(params):
        return tuple(t.init(params) for t in transforms)

    def update(g, states, params=None):
        new_states = []
        for t, s in zip(transforms, states):
            g, s = t.update(g, s, params)
            new_states.append(s)
        return g, tuple(new_states)

    return Optimizer(init, update)


def multi_transform(rules: list[tuple[str, Optimizer]], default: Optimizer) -> Optimizer:
    """Route params to optimizers by path regex (first match wins)."""
    def route(path: str) -> Optimizer:
        for pat, opt in rules:
            if re.search(pat, path):
                return opt
        return default

    def _paths(tree):
        # SparseGrad leaves stay opaque so a sparse pool grad routes by the
        # pool's own path (e.g. 'embedding/memory'), like its dense twin
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=_is_sparse)
        paths = ["/".join(str(getattr(k, "key", k)) for k in kp) for kp, _ in flat]
        return paths, [v for _, v in flat], treedef

    def init(params):
        paths, leaves, treedef = _paths(params)
        return tuple(route(p).init(l) for p, l in zip(paths, leaves))

    def update(g, states, params=None):
        paths, gleaves, treedef = _paths(g)
        pleaves = jax.tree_util.tree_leaves(params) if params is not None else gleaves
        outs, new_states = [], []
        for p, gl, pl, s in zip(paths, gleaves, pleaves, states):
            u, ns = route(p).update(gl, s, pl)
            outs.append(u)
            new_states.append(ns)
        return jax.tree_util.tree_unflatten(treedef, outs), tuple(new_states)

    return Optimizer(init, update)


# ------------------------------------------------------------------- schedules

def warmup_cosine(peak_lr: float, warmup: int, total: int, floor: float = 0.0):
    def schedule(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor + (peak_lr - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)

    return schedule


def constant(lr: float):
    return lambda step: jnp.asarray(lr, jnp.float32)
