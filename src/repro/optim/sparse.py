"""Sparse-gradient engine for the memory pool: end the O(m) per-step tax.

The paper's premise makes the pool ``M`` the dominant parameter, yet the
dense training step materializes a full [m] gradient (the lookup VJP
scatter-adds into ``zeros(m)``) and then runs an O(m) optimizer pass over
every slot — while a batch touches at most ``B*L*d << m`` unique locations.
This module never builds the [m] gradient, and updates the pool either in
O(K) random accesses or, where K is large against m, in one streaming pass
a block of stripes at a time:

``SparseGrad``
    A registered pytree (children ``indices [K]`` / ``values [K, ...]``,
    aux ``dense_shape`` / ``unique`` / ``buckets``) carrying the gradient
    of one pool in one of two sorted layouts: deduped (``unique=True`` —
    sorted unique slot ids, sentinel-padded, segment-summed values) or
    bucketed (``unique=False`` — sorted with duplicates, built stripe-major
    by ``from_bucketed_locations`` without any global argsort; duplicates
    fold inside the update kernel).  ``densify()`` is the exact dense
    oracle the parity tests compare against, for both layouts.

``sparse_value_and_grad(loss_fn)``
    Drop-in for ``jax.value_and_grad(loss_fn, has_aux=True)`` that returns
    ``SparseGrad`` leaves for every ``memory`` pool the loss looked up.
    A cotangent of an array primal must be an array of the same shape in
    JAX, so the sparse grad cannot come out of a custom VJP directly; the
    engine instead runs two passes inside the one jit trace:

      1. *record* — trace ``loss_fn`` once with the embed layer in record
         mode: each memory lookup reports its [N, d] location tensor (pure
         hashing — the fused engine's in-kernel location math, emitted
         instead of consumed) and returns zeros, so XLA dead-code-eliminates
         everything except the hashes;
      2. *provide* — differentiate the real loss with the pool behind
         ``stop_gradient`` plus an additive zero *tap* at each lookup
         output.  ``dL/dtap`` is exactly the per-location gradient values;
         the dense pool cotangent is a dead zeros leaf that the SparseGrad
         replaces before anything consumes it, so it never reaches HBM.

    Locations + tap grads become one ``SparseGrad`` per pool: striped-lma
    pools take the bucketed build (``from_bucketed_locations`` — d
    per-stripe stable key/value sorts, 7-9x cheaper than the flat path at
    K=2^13..2^17), everything else the flat on-device dedup
    (``dedup_locations``: sort + segment-sum).

``sparse_sgd`` / ``sparse_adagrad`` / ``sparse_rowwise_adam``
    Optimizers with two update paths for a sparse leaf, picked at trace
    time from the stream's layout and size (``stripe_blocked_ok``):

      * gather/scatter — gather the moments at the K touched slots,
        update them, scatter them back, and hand ``apply_updates`` K update
        values to scatter into the parameter (``repro/kernels/
        sparse_update``: the jnp reference on TPU and CPU), with lazy
        semantics: untouched slots' moments are bit-untouched, matching
        Adagrad's classic sparse rule (for Adagrad and momentum-less SGD
        this is *exactly* the dense update).  Every algorithm, unstriped
        pools, the sharded 'model'-mesh path and small streams take it.
      * stripe-blocked — Adagrad on a striped pool's bucketed stream with
        no 'model' mesh and ``K * STREAM_C >= m`` streams the pool instead
        (``stripe_blocked_adagrad``): per block of whole stripes, the
        block's stream slice folded to one sum per touched slot and
        written into zeros, then dense Adagrad over the block's pool and
        accumulator, written back in place.  Untouched slots come out bit-unchanged; the update is the
        pool's new value (``NewValue``), which ``apply_updates`` takes as
        it is.  On a TPU v5e one random access costs as much as streaming
        several hundred slots, so past K/m of about 0.2% this is faster.

    Dense leaves fall back to the matching dense math, so one optimizer
    instance serves a mixed tree; the dense optimizers in
    ``optimizers.py`` symmetrically delegate SparseGrad leaves here.  Each
    pool leaf's path is tallied in ``repro.obs`` as it traces
    (``pool_update.stripe_blocked`` / ``pool_update.gather_scatter``).

Under a distribution mesh with a non-trivial 'model' axis the moment
update and the parameter scatter run as masked-local shard_map bodies on
each device's slab (``repro/dist/sharded_memory.py``) — no [m_local] dense
gradient, no psum of it.  The update-value exchange between the two is
picked by ``repro.dist.exchange.resolve_update_exchange``: all_to_all by
default, which elides even the [K]-sized psum — each rank's owner-masked
update values feed the masked local scatter directly (the values are then
owner-partial: only ``sharded_sparse_apply`` may consume them).
Slab-aligned bucketed streams (``buckets % n_model == 0`` — see
``sharded_memory.slab_aligned``) go further: indices and values enter the
shard_map already 'model'-sharded and the whole update/apply round-trip
runs with zero exchange collectives.  ``REPRO_DIST_EXCHANGE=psum``
restores the replicated-update oracle on the non-aligned paths.

Gate: ``REPRO_SPARSE_GRADS`` (default on; ``=0`` keeps the dense path as
the bit-exact oracle).  Tests may toggle ``sparse.ENABLED`` directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.optim.optimizers import Optimizer, _Pair, _split_pairs

ENABLED = os.environ.get("REPRO_SPARSE_GRADS", "1").lower() not in (
    "0", "false", "off", "no")


def sparse_enabled() -> bool:
    return ENABLED


# ---------------------------------------------------------------- SparseGrad

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseGrad:
    """Sorted sparse gradient of one dense parameter (usually the pool M).

    Two static layouts, distinguished by the ``unique`` aux flag:

    ``unique=True`` (the deduped contract): ``indices`` are sorted *unique*
    slot ids compacted to the front and padded at the tail with the sentinel
    ``dense_shape[0]``; ``values`` are the segment-summed contributions
    (0 at padded slots).

    ``unique=False`` (the bucketed fast path): ``indices`` are sorted
    non-decreasing but may repeat (no sentinel padding) — coincident slots
    are folded *inside* the sparse-update kernel's gather->update->scatter
    pass instead of by a standalone O(K log K) dedup.  ``densify()`` is
    exact either way (scatter-add sums duplicates).

    ``buckets`` (static, nonzero only with ``unique=False``) records that
    the stream is *stripe-major*: bucket j's entries occupy the contiguous
    slice ``[j*K/buckets, (j+1)*K/buckets)`` and index only slots
    ``[j*m/buckets, (j+1)*m/buckets)``.  When ``buckets`` divides the model
    mesh size the even [K] split therefore lands each rank's slice exactly
    on its parameter slab — the sharded update/apply path runs with no
    collective at all (see repro.dist.sharded_memory.slab_aligned).
    """

    indices: jax.Array            # [K] int32, sorted (see ``unique``)
    values: jax.Array             # [K, *dense_shape[1:]] contributions
    dense_shape: tuple[int, ...]  # static (pytree aux)
    unique: bool = True           # static (pytree aux)
    buckets: int = 0              # static (pytree aux), stripe-major count

    def tree_flatten(self):
        return ((self.indices, self.values),
                (self.dense_shape, self.unique, self.buckets))

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, unique, buckets = aux
        return cls(children[0], children[1], tuple(shape), unique, buckets)

    @property
    def sentinel(self) -> int:
        return int(self.dense_shape[0])

    def densify(self) -> jax.Array:
        """The dense oracle: scatter-add into zeros(dense_shape)."""
        z = jnp.zeros(self.dense_shape, self.values.dtype)
        return z.at[self.indices].add(self.values, mode="drop")

    def map_values(self, fn) -> "SparseGrad":
        return SparseGrad(self.indices, fn(self.values), self.dense_shape,
                          self.unique, self.buckets)

    def all_finite(self, max_abs: float | None = None) -> jax.Array:
        """Scalar bool: every contribution finite (and ``<= max_abs`` when
        given).  Sound for both layouts: sentinel-padded tails carry exact
        zeros (``unique=True``) and bucketed streams are all real entries
        (``unique=False``), so no masking is needed."""
        ok = jnp.all(jnp.isfinite(self.values))
        if max_abs is not None:
            ok = ok & jnp.all(jnp.abs(self.values) <= max_abs)
        return ok


def is_sparse(x) -> bool:
    return isinstance(x, SparseGrad)


def dedup_locations(loc: jax.Array, vals: jax.Array,
                    dense_shape: tuple[int, ...]) -> SparseGrad:
    """On-device dedup: sort locations, segment-sum coincident values.

    ``loc``: [K] int slot ids (duplicates allowed), ``vals``: [K, ...]
    matching contributions.  Returns sorted unique indices compacted to the
    front, padded with the sentinel ``dense_shape[0]`` (values 0 there) —
    static [K] shapes throughout, jit-safe.
    """
    k = int(loc.shape[0])
    order = jnp.argsort(loc)
    si = jnp.take(loc, order).astype(jnp.int32)
    sv = jnp.take(vals, order, axis=0)
    head = jnp.concatenate([jnp.ones((1,), bool), si[1:] != si[:-1]])
    seg = jnp.cumsum(head) - 1                       # [K] ids in [0, K)
    # seg is a cumsum of 0/1 flags -> monotonically non-decreasing, so the
    # segment reduction can skip its own sort-or-scatter path
    summed = jax.ops.segment_sum(sv, seg, num_segments=k,
                                 indices_are_sorted=True)
    idx = jnp.full((k,), dense_shape[0], jnp.int32).at[seg].set(si)
    return SparseGrad(idx, summed, tuple(dense_shape))


def from_locations(loc: jax.Array, vals: jax.Array,
                   dense_shape: tuple[int, ...]) -> SparseGrad:
    """[..., d] location tensor + matching cotangent values -> SparseGrad."""
    trailing = len(dense_shape) - 1
    if trailing:
        vals = vals.reshape((-1,) + tuple(dense_shape[1:]))
        loc = loc.reshape(-1)
    else:
        loc, vals = loc.reshape(-1), vals.reshape(-1)
    return dedup_locations(loc, vals, dense_shape)


def _bucket_sharding(*arrs, axes: int = 1):
    """Attack (c): under a model mesh, pin bucket-major operands to the
    'model' axis so each device sorts only its d/n stripes — no device ever
    sorts (or holds) the global K.  ``axes=1`` shards [d, N] matrices on
    dim 0; ``axes=0`` shards flat stripe-major [K] streams, whose even split
    coincides with the parameter-slab ownership (see
    ``repro.dist.sharded_memory.slab_aligned``)."""
    from repro.dist import context as dctx
    from repro.dist.exchange import model_size
    mesh = dctx.current_mesh()
    if mesh is None or model_size(mesh) <= 1:
        return arrs if len(arrs) > 1 else arrs[0]
    P = jax.sharding.PartitionSpec
    spec = P("model", None) if axes else P("model")
    out = []
    for a in arrs:
        divisible = a.shape[0] % model_size(mesh) == 0
        out.append(jax.lax.with_sharding_constraint(
            a, jax.sharding.NamedSharding(mesh, spec)) if divisible else a)
    return tuple(out) if len(out) > 1 else out[0]


def _model_replicated(x):
    """Under a model mesh, pin ``x`` [N, ...] to the batch layout: rows
    over the data-parallel axes, replicated over 'model'.  The
    lookup-output gradients arrive in that layout; without the pin the
    bucket sort's 'model' constraint propagates back into the model's
    backward pass, which XLA then partitions along d and rounds
    differently from the single-device step."""
    from repro.dist import context as dctx
    from repro.dist.exchange import model_size
    mesh = dctx.current_mesh()
    if mesh is None or model_size(mesh) <= 1:
        return x
    dp = dctx.dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    lead = dp if dp and x.shape[0] % n_dp == 0 else None
    spec = jax.sharding.PartitionSpec(lead, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def from_bucketed_locations(loc: jax.Array, vals: jax.Array,
                            dense_shape: tuple[int, ...]) -> SparseGrad:
    """Bucketed (striped-layout) fast path: [N, d] locations whose column j
    is confined to stripe ``[j*(m//d), (j+1)*(m//d))`` -> a sorted-with-
    duplicates ``SparseGrad`` (``unique=False``, ``buckets=d``) without any
    global argsort.

    Column-major emission is location-bucketed by construction (duplicates
    never cross stripes), so a *batched* per-stripe stable key/value sort
    of [d, N] offset rows yields a globally sorted index stream — measured
    7-10x cheaper than the flat argsort + segment-sum dedup at K=131k
    (bench ``sparse_dedup_sort`` sweep).  Values ride along as a second
    ``lax.sort`` operand, so under a model mesh the sort stays stripe-local
    (no cross-device payload gather).  The remaining duplicate fold happens
    inside the sparse-update kernel (``kernels/sparse_update``), or in
    ``dedup_locations``-equivalent semantics via ``densify``.

    Falls back to ``from_locations`` for trailing dims or a ragged budget
    (m % d != 0).
    """
    if len(dense_shape) != 1 or loc.ndim != 2:
        return from_locations(loc, vals, dense_shape)
    m = int(dense_shape[0])
    n, d = int(loc.shape[0]), int(loc.shape[1])
    if n == 0 or d == 0 or m % d != 0:
        return from_locations(loc, vals, dense_shape)
    stripe = m // d
    col = jnp.arange(d, dtype=jnp.int32)[:, None]
    lT = loc.T.astype(jnp.int32)                     # [d, N] bucket-major
    vT = _model_replicated(vals).reshape(n, d).T
    off = (lT - col * stripe).astype(jnp.uint32)     # in-stripe offsets
    off, vT = _bucket_sharding(off, vT, axes=1)
    # d independent stable sorts; stability keeps coincident slots in
    # emission order, matching the packed-key oracle bit-for-bit
    soff, sval = jax.lax.sort((off, vT), dimension=1, num_keys=1,
                              is_stable=True)
    sloc = soff.astype(jnp.int32) + col * stripe
    idx, v = _bucket_sharding(sloc.reshape(-1), sval.reshape(-1), axes=0)
    return SparseGrad(idx, v, tuple(dense_shape), unique=False, buckets=d)


# ------------------------------------------------------- trace-time contexts
#
# The embed layer (repro/embed/table.py::_memory_lookup) cooperates through a
# module-level stack: ``record`` collects (pool leaf, locations) pairs,
# ``provide`` hands each lookup its additive zero tap in call order.  All
# tracers involved live in the surrounding jit trace, so closing over them
# is safe; the stack is trace-time-only Python state (never crosses a jit
# boundary).

_STACK: list = []


@dataclasses.dataclass
class _Record:
    memory: jax.Array             # the pool leaf (trace-time identity key)
    loc: jax.Array                # [N, d] element locations, or [N] row ids
    tap_shape: tuple              # the lookup output shape the tap rides on
    dtype: jnp.dtype
    row_width: int = 0            # d when loc is [N] row ids, else 0
    n_buckets: int = 0            # d when loc columns are stripe-bucketed
    #                               (LMAParams.striped layout), else 0


class _Recorder:
    mode = "record"

    def __init__(self):
        self.records: list[_Record] = []

    def record(self, memory, loc, n_buckets: int = 0):
        """Element-level locations [N, d] (lma-style hashing).

        ``n_buckets=d`` declares the striped-layout invariant: column j of
        ``loc`` lies in ``[j*(m//d), (j+1)*(m//d))``, enabling the bucketed
        dedup-free SparseGrad build (``from_bucketed_locations``)."""
        self.records.append(_Record(memory, loc, tuple(loc.shape),
                                    memory.dtype, n_buckets=n_buckets))

    def record_rows(self, memory, rows, d: int):
        """Row-aligned pool rows [N] (hashed_row / freq): one index per row,
        the [N, d] tap grad becomes the row delta directly."""
        self.records.append(_Record(memory, rows, (rows.shape[0], d),
                                    memory.dtype, row_width=d))


class _Provider:
    mode = "provide"

    def __init__(self, taps):
        self._taps = list(taps)
        self._i = 0

    def next_tap(self, shape):
        assert self._i < len(self._taps), (
            "sparse-grad provide pass saw more memory lookups than the "
            "record pass — loss_fn must be deterministic in its call order")
        tap = self._taps[self._i]
        self._i += 1
        assert tap.shape == tuple(shape), (tap.shape, shape)
        return tap


@contextlib.contextmanager
def _tracing(obj):
    _STACK.append(obj)
    try:
        yield obj
    finally:
        _STACK.pop()


def active():
    """The innermost active sparse-trace context, or None (normal mode)."""
    return _STACK[-1] if _STACK else None


# ----------------------------------------------------------- grad transform

def _is_memory_key(kp) -> bool:
    last = kp[-1]
    return str(getattr(last, "key", last)) == "memory"


def has_memory(params) -> bool:
    """Does the tree hold any 'memory'-named pool leaf?"""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return any(_is_memory_key(kp) for kp, _ in flat)


def sparse_value_and_grad(loss_fn: Callable, has_aux: bool = True):
    """``fn(params, *args) -> ((loss, aux), grads)`` with SparseGrad leaves
    for every memory pool the loss looked up; all other leaves dense.

    Falls back to plain ``jax.value_and_grad`` when nothing records (table-
    family schemes, or a loss with no embedding at all).

    Constraints: ``loss_fn`` must be trace-deterministic (same lookup call
    order every trace); memory lookups must not sit inside lax control-flow
    bodies (scan/while) — the recorded location tracers must live at the
    loss function's own trace level; and every gradient path into a pool
    must go through the embed lookups — the SparseGrad *replaces* the
    pool's cotangent, so a direct read of ``params[...]["memory"]`` in the
    loss (e.g. an L2 penalty on the raw pool) would have its gradient
    dropped.  Regularize through the lookup outputs instead, or run the
    dense oracle.  Every model in this repo satisfies all three
    (retrieval's scan does no training lookups; nothing reads M directly).
    """

    def vg(params, *args):
        rec = _Recorder()
        with _tracing(rec), jax.named_scope("record"):
            loss_fn(params, *args)
        if not rec.records:
            return jax.value_and_grad(loss_fn, has_aux=has_aux)(params, *args)

        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        path_of = {id(leaf): kp for kp, leaf in flat}
        groups: dict = {}
        for i, r in enumerate(rec.records):
            kp = path_of.get(id(r.memory))
            assert kp is not None, (
                "recorded memory pool is not a leaf of params")
            groups.setdefault(kp, []).append(i)

        taps = [jnp.zeros(r.tap_shape, r.dtype) for r in rec.records]

        def lf(p, taps_):
            with _tracing(_Provider(taps_)):
                return loss_fn(p, *args)

        with jax.named_scope("provide"):
            out, (gp, gt) = jax.value_and_grad(
                lf, argnums=(0, 1), has_aux=has_aux)(params, taps)
        with jax.named_scope("sparse_grad"):
            replace = _build_sparse_grads(rec.records, groups, gt, flat)

        # swap the dead dense pool cotangents (zeros under stop_gradient —
        # unused after this, so XLA never materializes them) for SparseGrads
        gflat, gdef = jax.tree_util.tree_flatten_with_path(gp)
        leaves = [replace.get(kp, v) for kp, v in gflat]
        grads = jax.tree_util.tree_unflatten(gdef, leaves)
        return out, grads

    return vg


def _build_sparse_grads(records, groups, gt, flat) -> dict:
    """{pool path: SparseGrad} from the record pass's locations and the
    provide pass's tap cotangents ``gt``, one build per pool."""
    leaf_shape = {kp: leaf.shape for kp, leaf in flat}
    replace = {}
    for kp, idxs in groups.items():
        rws = {records[i].row_width for i in idxs}
        assert len(rws) == 1, (
            "one memory pool mixes row- and element-level sparse "
            "records; schemes must be consistent per pool")
        (rw,) = rws
        m = int(leaf_shape[kp][0])
        if rw:                                  # row-aligned pool
            rows = jnp.concatenate([records[i].loc.reshape(-1) for i in idxs])
            vals = jnp.concatenate([gt[i].reshape(-1, rw) for i in idxs])
            replace[kp] = from_locations(rows, vals, (m // rw, rw))
            continue
        nbs = {records[i].n_buckets for i in idxs}
        nb = nbs.pop() if len(nbs) == 1 else 0
        if nb and all(records[i].loc.ndim == 2
                      and records[i].loc.shape[1] == nb
                      for i in idxs) and len(leaf_shape[kp]) == 1:
            loc = jnp.concatenate([records[i].loc for i in idxs], axis=0)
            vals = jnp.concatenate([gt[i].reshape(-1, nb) for i in idxs],
                                   axis=0)
            replace[kp] = from_bucketed_locations(loc, vals,
                                                  tuple(leaf_shape[kp]))
        else:
            loc = jnp.concatenate([records[i].loc.reshape(-1) for i in idxs])
            vals = jnp.concatenate([gt[i].reshape(-1) for i in idxs])
            replace[kp] = from_locations(loc, vals, tuple(leaf_shape[kp]))
    return replace


# ------------------------------------------------------------- mesh routing

def _model_mesh(n_slots: int):
    """Mesh with a non-trivial 'model' axis dividing the slab, else None."""
    from repro.dist import context as dctx
    from repro.dist.exchange import model_size
    mesh = dctx.current_mesh()
    if mesh is None:
        return None
    n_model = model_size(mesh)
    if n_model <= 1 or n_slots % n_model != 0:
        return None
    return mesh


def _pool_view(arr: jax.Array, shape: tuple):
    """View a flat [m] pool/state as the SparseGrad's (rows, d) layout."""
    shape = tuple(shape)
    if arr.shape == shape:
        return arr
    assert arr.size == int(np.prod(shape)), (arr.shape, shape)
    return arr.reshape(shape)


@jax.named_scope("pool_update")
def _leaf_sparse_update(algo: str, g: SparseGrad, states: tuple, **hyper):
    """One sparse leaf through the kernel (or the sharded slab path)."""
    obs.count("pool_update.gather_scatter")
    orig_shapes = tuple(s.shape for s in states)
    states = tuple(_pool_view(s, g.dense_shape) for s in states)
    mesh = _model_mesh(g.dense_shape[0]) if states else None
    if mesh is not None:
        from repro.dist.sharded_memory import sharded_sparse_update
        u, new_states = sharded_sparse_update(algo, g.indices, g.values,
                                              states, hyper, mesh,
                                              unique=g.unique,
                                              buckets=g.buckets)
    else:
        from repro.kernels.sparse_update.ops import sparse_update
        u, new_states = sparse_update(algo, g.indices, g.values, states,
                                      unique=g.unique, **hyper)
    new_states = tuple(s.reshape(shp)
                       for s, shp in zip(new_states, orig_shapes))
    return g.map_values(lambda _: u), new_states


@jax.named_scope("pool_update")
def sparse_apply(p: jax.Array, u: SparseGrad) -> jax.Array:
    """``apply_updates`` for one sparse leaf: O(K) scatter-add into p."""
    vals = u.values.astype(p.dtype)
    pv = _pool_view(p, u.dense_shape)
    mesh = _model_mesh(u.dense_shape[0])
    if mesh is not None:
        from repro.dist.sharded_memory import sharded_sparse_apply
        out = sharded_sparse_apply(pv, u.indices, vals, mesh,
                                   unique=u.unique, buckets=u.buckets)
    else:
        out = pv.at[u.indices].add(vals, mode="drop",
                                   indices_are_sorted=True)
    return out.reshape(p.shape)


# ------------------------------------------------- stripe-blocked Adagrad

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NewValue:
    """An update that already is the parameter's new value.

    The stripe-blocked Adagrad computes a pool's new value inside its own
    pass, so ``apply_updates`` takes this leaf as it is.  Nothing may
    transform it after the optimizer: ``optimizers._gmap`` raises on it."""

    value: jax.Array

    def tree_flatten(self):
        return (self.value,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])


# m/K at which both Adagrad paths take the same time on a TPU v5e (fitted
# from their timings, PERF.md section 6, PR 14): the stripe-blocked path
# runs when K * STREAM_C >= m
STREAM_C = 500
# largest block (whole stripes, a divisor of their count) the pass writes
# into zeros: a one-stripe block of dlrm-rm2's pool, 8.4 MB, is the
# fastest on a TPU v5e
BLOCK_BYTES = 16 << 20


def stripe_blocked_ok(g: SparseGrad, p) -> bool:
    """Does Adagrad on ``g`` stream the pool stripe block by stripe block?

    Yes for a bucketed stream (a striped pool) with no model mesh, whose
    stripes fit a block and whose K entries are many against the m slots.
    Everything else keeps the gather/scatter pass."""
    if p is None or not g.buckets or g.unique or len(g.dense_shape) != 1:
        return False
    m = int(g.dense_shape[0])
    stripe_bytes = m // g.buckets * np.dtype(np.float32).itemsize
    return (stripe_bytes <= BLOCK_BYTES and _model_mesh(m) is None
            and int(g.indices.shape[0]) * STREAM_C >= m)


@jax.named_scope("pool_update")
def stripe_blocked_adagrad(g: SparseGrad, acc: jax.Array, p: jax.Array, *,
                           lr, eps):
    """Adagrad on a bucketed stream as one dense pass over the pool, a
    block of whole stripes at a time -> (NewValue(new p), new acc).

    Per block: fold the block's slice of the stripe-major stream (sorted
    inside each stripe) into one sum per touched slot, write the sums into
    a zero block, then ``acc += g*g`` and ``p += -lr*g/(sqrt(acc)+eps)``
    over the block's slots, back in place into the loop-carried pool and
    accumulator.  Untouched slots get ``acc + 0`` and ``p + (-0.0)``:
    bit-unchanged.  Touched slots get ``sparse_adagrad_ref``'s formula on
    the same folded sums."""
    from repro.kernels.sparse_update.ref import fold_duplicates
    obs.count("pool_update.stripe_blocked")
    d, m = g.buckets, int(g.dense_shape[0])
    stripe = m // d
    s = max(b for b in range(1, d + 1)
            if d % b == 0 and (b == 1 or b * stripe * 4 <= BLOCK_BYTES))
    idx = g.indices.reshape(d // s, -1)
    val = g.values.reshape(d // s, -1)
    lr = jnp.asarray(lr, jnp.float32)
    n = s * stripe

    def block(b, state):
        acc, p = state
        lo = b * n
        i = jax.lax.dynamic_index_in_dim(idx, b, keepdims=False) - lo
        head, v = fold_duplicates(i, jax.lax.dynamic_index_in_dim(
            val, b, keepdims=False))
        # one write per touched slot, of its run's sum: the other entries
        # go past the block and are dropped, so the indices are unique and
        # a set replaces the add (~40% faster on a TPU v5e, PERF.md)
        i = jnp.where(head, i, n + jnp.arange(i.shape[0], dtype=i.dtype))
        gf = jnp.zeros((n,), v.dtype).at[i].set(
            v, unique_indices=True, mode="drop").astype(jnp.float32)
        ds = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=lo, slice_size=n)
        acc = jax.lax.dynamic_update_slice_in_dim(
            acc, ds(acc) + jnp.square(gf), lo, 0)
        # the pool's write reads the block back from the updated
        # accumulator: reading the old one would race its in-place write,
        # and XLA would copy the whole accumulator to order the two
        u = (-lr * gf / (jnp.sqrt(ds(acc)) + eps)).astype(v.dtype)
        return acc, jax.lax.dynamic_update_slice_in_dim(
            p, ds(p) + u.astype(p.dtype), lo, 0)

    with jax.named_scope("stripe_blocked"):
        acc_, p_ = jax.lax.fori_loop(0, d // s, block,
                                     (acc.reshape(-1), p.reshape(-1)))
    return NewValue(p_.reshape(p.shape)), acc_.reshape(acc.shape)


# -------------------------------------------------- leaf update entry points
# (shared by the sparse optimizers below AND the dense optimizers'
# SparseGrad delegation in optimizers.py — one implementation, no drift)

def sgd_leaf(g, mo, p=None, *, lr, momentum=0.0):
    if is_sparse(g):
        states = () if mo is None or momentum == 0.0 else (mo,)
        u, new = _leaf_sparse_update("sgd", g, states, lr=lr,
                                     momentum=momentum)
        return u, (new[0] if new else mo)
    if momentum == 0.0:
        return -lr * g, mo
    mo = momentum * mo + g
    return -lr * mo, mo


def adagrad_leaf(g, acc, p=None, *, lr, eps=1e-10):
    """Adagrad on one leaf.  A sparse leaf with its parameter ``p`` at
    hand takes ``stripe_blocked_adagrad`` where ``stripe_blocked_ok``
    says so (the update is then a ``NewValue``), else the gather/scatter
    pass (a ``SparseGrad`` of update values)."""
    if is_sparse(g):
        if stripe_blocked_ok(g, p):
            return stripe_blocked_adagrad(g, acc, p, lr=lr, eps=eps)
        u, (acc,) = _leaf_sparse_update("adagrad", g, (acc,), lr=lr, eps=eps)
        return u, acc
    acc = acc + jnp.square(g.astype(jnp.float32))
    return (-lr * g / (jnp.sqrt(acc) + eps)).astype(g.dtype), acc


def adagrad_tree(g, acc, p=None, *, lr, eps):
    """(updates, acc) of ``adagrad_leaf`` over a gradient tree, each leaf
    with its parameter when ``p`` is given."""
    if p is None:
        return _split_pairs(_tmap(
            lambda x, a: _Pair(*adagrad_leaf(x, a, lr=lr, eps=eps)), g, acc))
    return _split_pairs(_tmap(
        lambda x, a, q: _Pair(*adagrad_leaf(x, a, q, lr=lr, eps=eps)),
        g, acc, p))


def adam_leaf(g, mu, nu, p=None, *, lr, b1=0.9, b2=0.999, bc1=1.0, bc2=1.0,
              eps=1e-8, weight_decay=0.0):
    """Lazy Adam on a sparse leaf (rowwise nu when it is stored rowwise);
    dense leaves get the same formulas applied everywhere (== dense Adam
    when nu is elementwise).  Decoupled weight decay is lazy too: only the
    touched slots decay, gathered from ``p`` at the sparse indices."""
    if is_sparse(g):
        u, (mu, nu) = _leaf_sparse_update("adam", g, (mu, nu), lr=lr, b1=b1,
                                          b2=b2, bc1=bc1, bc2=bc2, eps=eps)
        if weight_decay and p is not None:
            pv = _pool_view(p, g.dense_shape)
            rows = jnp.take(pv, jnp.minimum(g.indices, pv.shape[0] - 1),
                            axis=0).astype(jnp.float32)
            keep = g.indices < pv.shape[0]
            if not g.unique:
                # non-unique indices scatter-add: decay each slot once, at
                # the head of its duplicate run
                keep = keep & jnp.concatenate(
                    [jnp.ones((1,), bool), g.indices[1:] != g.indices[:-1]])
            keep = keep.reshape((-1,) + (1,) * (u.values.ndim - 1))
            u = u.map_values(
                lambda v: v - jnp.where(keep, lr * weight_decay * rows, 0.0))
        return u, mu, nu
    gf = g.astype(jnp.float32)
    mu = b1 * mu + (1 - b1) * gf
    v2 = jnp.square(gf)
    if nu.ndim == 1 and g.ndim > 1:                  # rowwise second moment
        nu = b2 * nu + (1 - b2) * jnp.mean(v2, axis=tuple(range(1, g.ndim)))
        nu_b = nu.reshape(nu.shape + (1,) * (g.ndim - 1))
    else:
        nu = b2 * nu + (1 - b2) * v2
        nu_b = nu
    u = -lr * (mu / bc1) / (jnp.sqrt(nu_b / bc2) + eps)
    if weight_decay and p is not None:
        u = u - lr * weight_decay * p.astype(jnp.float32)
    return u.astype(g.dtype), mu, nu


# --------------------------------------------------------- sparse optimizers

def _tmap(fn, grads, *rest):
    """tree_map with SparseGrad leaves opaque."""
    return jax.tree_util.tree_map(fn, grads, *rest, is_leaf=is_sparse)


def sparse_sgd(lr: float, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def update(g, s, p=None):
        if momentum == 0.0:
            return _tmap(lambda x: (x.map_values(lambda v: -lr * v)
                                    if is_sparse(x) else -lr * x), g), s
        return _split_pairs(_tmap(
            lambda x, m: _Pair(*sgd_leaf(x, m, lr=lr, momentum=momentum)),
            g, s))

    return Optimizer(init, update)


def sparse_adagrad(lr: float, eps: float = 1e-10,
                   initial_acc: float = 0.0) -> Optimizer:
    """Lazy Adagrad: same ``initial_acc``/``eps`` contract as the dense
    ``optimizers.adagrad`` (the shared parametrized test pins this), with
    the per-step cost O(K) instead of O(m)."""

    def init(params):
        return jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, initial_acc, dtype=jnp.float32),
            params)

    def update(g, acc, p=None):
        return adagrad_tree(g, acc, p, lr=lr, eps=eps)

    return Optimizer(init, update)


class RowwiseAdamState(NamedTuple):
    step: jax.Array
    mu: object
    nu: object


def sparse_rowwise_adam(lr: float, b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8) -> Optimizer:
    """Lazy Adam with a row-wise second moment (one nu scalar per leading
    index — for the flat pool each slot is its own row, i.e. elementwise).
    Bias correction uses the global step; untouched rows keep stale moments
    (SparseAdam semantics)."""

    def init(params):
        mu = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        nu = jax.tree_util.tree_map(
            lambda x: jnp.zeros((x.shape[0],) if x.ndim > 1 else x.shape,
                                jnp.float32), params)
        return RowwiseAdamState(jnp.zeros((), jnp.int32), mu, nu)

    def update(g, state, p=None):
        step = state.step + 1
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        leaves, td = jax.tree_util.tree_flatten(g, is_leaf=is_sparse)
        mus = td.flatten_up_to(state.mu)
        nus = td.flatten_up_to(state.nu)
        outs = [adam_leaf(x, m, n, lr=lr, b1=b1, b2=b2, bc1=bc1, bc2=bc2,
                          eps=eps) for x, m, n in zip(leaves, mus, nus)]
        unf = lambda i: jax.tree_util.tree_unflatten(
            td, [o[i] for o in outs])
        return unf(0), RowwiseAdamState(step, unf(1), unf(2))

    return Optimizer(init, update)
