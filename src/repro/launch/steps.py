"""Step builders + ShapeDtypeStruct input specs for every (arch x shape) cell.

``build_cell(arch_id, shape_id, mesh)`` returns a Bundle with:
  fn          — the step function to jit (train_step / prefill / serve_step /
                forward / retrieval)
  args        — ShapeDtypeStruct pytree (no device allocation)
  in_shardings / out_shardings — NamedShardings per DESIGN.md section 5
  donate      — argnums to donate (params/opt for train, cache for decode)

The same builders power the real launchers (train.py / serve.py) with concrete
arrays instead of specs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, get_config
from repro.configs.gat_cora import GNN_SHAPE_TABLE
from repro.configs._lm_common import LM_SHAPE_TABLE
from repro.configs._recsys_common import RECSYS_SHAPE_TABLE
from repro.dist import exchange as exl
from repro.dist import sharding as shd
from repro.dist.sharding import ALL, DP, EP
from repro.models import gnn, recsys, transformer
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sparse_lib

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class Bundle:
    arch_id: str
    shape_id: str
    fn: Callable
    args: tuple
    in_shardings: tuple
    out_shardings: Any
    donate: tuple[int, ...] = ()
    meta: dict = dataclasses.field(default_factory=dict)


def make_optimizer(arch: ArchConfig):
    if arch.optimizer == "adafactor":
        return opt_lib.adafactor(arch.learning_rate)
    if arch.optimizer == "adam":
        return opt_lib.adam(arch.learning_rate)
    if arch.optimizer == "adagrad":
        return opt_lib.adagrad(arch.learning_rate)
    if arch.optimizer == "sgd":
        return opt_lib.sgd(arch.learning_rate, momentum=0.9)
    raise ValueError(arch.optimizer)


def _shardings(mesh, shapes, rules):
    return shd.shardings_for(mesh, shapes, rules)


def _rep(mesh, tree):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree,
        is_leaf=lambda x: isinstance(x, SDS))


def _fit_dp(mesh, n):
    """Batch PartitionSpec over dp axes if divisible, else replicate."""
    spec = shd.resolve_template([[DP, "data", None]], (n,), mesh)
    return spec


# ------------------------------------------------------------------------- LM

LM_CACHE_RULES = [
    # [count, B, L, (KV, hd | r+rd)] — cache LENGTH shards over 'model' plus
    # every dp axis the batch leaves idle (flash-decoding,
    # dist/flash_decode.py): works for every arch including qwen's 40 KV
    # heads, and spreads the B=1 long_500k cache over the full mesh
    (r"/(k|v)$", [None, [DP, "data", None], [ALL, EP, "model"], None, None]),
    (r"/ckv$", [None, [DP, "data", None], [ALL, EP, "model"], None]),
    # int8-cache scales: same (B, L) sharding as their cache
    (r"/(k|v)_scale$", [None, [DP, "data", None], [ALL, EP, "model"], None]),
    (r"/ckv_scale$", [None, [DP, "data", None], [ALL, EP, "model"]]),
]


def _lm_bundle(arch: ArchConfig, shape_id: str, mesh) -> Bundle:
    t = LM_SHAPE_TABLE[shape_id]
    tcfg = arch.make_model(shape_id)
    B, S = t["global_batch"], t["seq_len"]
    rules = shd.lm_rules()

    param_shapes = jax.eval_shape(
        lambda: transformer.init(jax.random.key(0), tcfg))
    param_sh = _shardings(mesh, param_shapes, rules)
    tok = SDS((B, S), jnp.int32)
    bspec = shd.resolve_template([[DP, "data", None], None], (B, S), mesh)
    tok_sh = NamedSharding(mesh, bspec)

    if t["kind"] == "train":
        optimizer = make_optimizer(arch)
        opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
        opt_sh = _shardings(mesh, opt_shapes, rules)

        def train_step(params, opt_state, batch):
            def lf(p):
                loss, m = transformer.loss_fn(p, tcfg, batch["tokens"],
                                              batch["labels"])
                return loss, m
            (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = opt_lib.apply_updates(params, updates)
            return params, opt_state, metrics["ce"]

        batch = {"tokens": tok, "labels": tok}
        batch_sh = {"tokens": tok_sh, "labels": tok_sh}
        return Bundle(
            arch.arch_id, shape_id, train_step,
            (param_shapes, opt_shapes, batch),
            (param_sh, opt_sh, batch_sh),
            (param_sh, opt_sh, NamedSharding(mesh, P())),
            donate=(0, 1), meta={"kind": "train", "tokens": B * S})

    if t["kind"] == "prefill":
        def prefill_step(params, tokens):
            return transformer.prefill(params, tcfg, tokens)

        cache_shapes = jax.eval_shape(
            lambda: transformer.init_cache(tcfg, B, S))
        cache_sh = _shardings(mesh, cache_shapes, LM_CACHE_RULES)
        logits_sh = NamedSharding(mesh, shd.resolve_template(
            [[DP, "data", None], ["model"]], (B, tcfg.vocab_size), mesh))
        return Bundle(
            arch.arch_id, shape_id, prefill_step,
            (param_shapes, tok),
            (param_sh, tok_sh),
            (logits_sh, cache_sh),
            meta={"kind": "prefill", "tokens": B * S})

    # decode (decode_32k / long_500k): one token against an S-long cache
    def serve_step(params, tokens, cache, cache_len):
        return transformer.decode_step(params, tcfg, tokens, cache, cache_len)

    cache_shapes = jax.eval_shape(lambda: transformer.init_cache(tcfg, B, S))
    cache_sh = _shardings(mesh, cache_shapes, LM_CACHE_RULES)
    tok1 = SDS((B,), jnp.int32)
    tok1_sh = NamedSharding(mesh, _fit_dp(mesh, B))
    len_spec = SDS((), jnp.int32)
    logits_sh = NamedSharding(mesh, shd.resolve_template(
        [[DP, "data", None], ["model"]], (B, tcfg.vocab_size), mesh))
    return Bundle(
        arch.arch_id, shape_id, serve_step,
        (param_shapes, tok1, cache_shapes, len_spec),
        (param_sh, tok1_sh, cache_sh, NamedSharding(mesh, P())),
        (logits_sh, cache_sh),
        donate=(2,), meta={"kind": "decode", "tokens": B})


# --------------------------------------------------------------------- recsys

def _recsys_batch_specs(rcfg, B: int, mesh):
    if rcfg.model == "din":
        batch = {"hist": SDS((B, rcfg.hist_len), jnp.int32),
                 "hist_mask": SDS((B, rcfg.hist_len), jnp.bool_),
                 "target": SDS((B,), jnp.int32),
                 "label": SDS((B,), jnp.float32)}
    else:
        batch = {"sparse": SDS((B, rcfg.n_fields), jnp.int32),
                 "label": SDS((B,), jnp.float32)}
        if rcfg.n_dense:
            batch["dense"] = SDS((B, rcfg.n_dense), jnp.float32)
    sh = {}
    for k, v in batch.items():
        tmpl = [[DP, "data", None]] + [None] * (len(v.shape) - 1)
        sh[k] = NamedSharding(mesh, shd.resolve_template(tmpl, v.shape, mesh))
    return batch, sh


def _recsys_buffer_specs(rcfg, mesh):
    """Buffer specs come from the scheme (Scheme.buffer_specs), not a
    hard-coded kind list — a registered scheme's buffers show up in every
    bundle automatically (lma's D' store, freq's hot-id table, ...)."""
    from repro.embed import get_scheme
    e = rcfg.embedding
    specs = get_scheme(e.kind).buffer_specs(e, store_rows(e.total_vocab))
    if not specs:
        return {}, {}
    bufs = {name: SDS(shape, jnp.dtype(dt))
            for name, (shape, dt) in specs.items()}
    sh = _shardings(mesh, bufs, shd.buffer_rules())
    return bufs, sh


def store_rows(total_vocab: int) -> int:
    """Dense-store rows padded so every mesh axis divides evenly (shard_map)."""
    return -(-total_vocab // 512) * 512


def _sparse_worthwhile(rcfg, B: int, mesh) -> bool:
    """Sparse-vs-dense pool-update gate, now owned by the exchange layer.

    The traffic model that used to live here moved to
    ``repro.dist.exchange.sparse_worthwhile``, next to the lookup-strategy
    resolver — one cost model for every cross-device exchange.  It prices
    the per-strategy sparse exchange (the all_to_all form keeps each rank's
    owned (index, value) slices local, ~n_model cheaper than the replicated
    psum pair) AND a per-path dedup term.  Net effect on the committed
    cells: single-host stays sparse; row-aligned schemes (hashed_row /
    freq) go sparse at pod scale (index traffic d times smaller); and
    16x16 element-level lma train cells — dense until the bucketed striped
    layout landed — now go sparse too: per-stripe sorts sharded over
    'model' plus the update kernel's in-kernel fold price the SparseGrad
    construction below the dense slab tax.  Only element schemes on a
    ragged budget (m % d != 0, ``sparse_buckets`` == 0) still pay the flat
    O(K log K) sort and stay dense at pod scale.
    """
    from repro.embed import get_scheme
    e = rcfg.embedding
    if e.budget is None:
        return False
    scheme = get_scheme(e.kind)
    return exl.sparse_worthwhile(
        mesh, n_lookups=B * recsys.lookups_per_example(rcfg), d=e.dim,
        m=e.budget, row_mode=scheme.row_aligned,
        buckets=scheme.sparse_buckets(e))


def _exchange_meta(rcfg, n_rows: int, mesh) -> dict:
    """Resolved lookup-exchange strategy + modeled per-device bytes for the
    dryrun artifact: ``n_rows`` is the per-step global row-lookup count; the
    resolver sees the per-device flat rows and the SAME ``alloc_row`` term
    the runtime driver passes (scheme set width + fused-slab AND
    fused-chunk eligibility), so the recorded strategy and per-strategy
    cost table match what actually lowers."""
    from repro.embed import get_scheme
    e = rcfg.embedding
    if e.budget is None:
        return {}
    dp = [int(mesh.shape[a]) for a in ("pod", "data") if a in mesh.axis_names]
    prod = int(np.prod(dp)) if dp else 1
    # divisibility on FLAT rows matches the runtime exactly: every embed
    # path flattens gids to 1-D before the driver (embed/table.py), so the
    # driver's _batch_axes sees this same n_rows as its leading dim
    n_flat = n_rows // prod if n_rows % prod == 0 else n_rows
    n_model = exl.model_size(mesh)
    alloc_row = exl.alloc_bytes_per_row(
        e.dim, set_width=get_scheme(e.kind).exchange_set_width(e))
    fused = exl.fused_slab_eligible(e.budget, n_model, e.jdtype.itemsize)
    fused_chunk = exl.fused_chunk_eligible(e.budget, n_model,
                                           e.jdtype.itemsize)
    ex = exl.resolve_exchange(mesh, B=n_flat, d=e.dim, m=e.budget,
                              alloc_row=alloc_row, fused=fused,
                              fused_chunk=fused_chunk)
    costs = exl.lookup_cost(n_model, n_flat, e.dim, alloc_row, fused=fused,
                            fused_chunk=fused_chunk)
    return {"exchange": ex.name,
            "exchange_fused_chunk": bool(fused_chunk),
            "exchange_modeled_bytes": {k: int(v) for k, v in costs.items()}}


def _sparse_meta(rcfg, B: int, mesh) -> dict:
    """Per-path sparse-update cost table for the dryrun artifact: the same
    ``sparse_update_cost`` call the gate ranks, so a recorded
    ``sparse_grads`` flag always has its pricing (dense slab tax vs psum /
    all_to_all sparse exchange, plus the dedup term actually charged —
    flat, bucketed, or bucket-sharded) sitting next to it in meta."""
    from repro.embed import get_scheme
    e = rcfg.embedding
    if e.budget is None:
        return {}
    scheme = get_scheme(e.kind)
    costs = exl.sparse_update_cost(
        exl.model_size(mesh), B * recsys.lookups_per_example(rcfg), e.dim,
        e.budget, row_mode=scheme.row_aligned,
        buckets=scheme.sparse_buckets(e))
    return {"sparse_update_modeled_bytes":
            {k: int(v) for k, v in costs.items()}}


def _tier_meta(rcfg, B: int, mesh=None) -> dict:
    """Tier split + modeled host-fetch traffic for the dryrun artifact.

    Always emitted for memory-pool train cells so the artifact records the
    tiering posture the cell would launch with: no budget (or a pool that
    fits) lowers as all-hot with zero host traffic, and xdeepfm — whose
    dual memory pools the launcher refuses to tier — records an explicit
    skipped marker instead of a split it would never apply.  The split
    comes from the same ``tier_split`` rule the launcher applies (budget
    over both compact leaves plus their stage regions), and the byte model
    from ``exchange.tier_fetch_bytes`` — staged cold blocks are bounded by
    one block per per-device location element (set schemes read
    ``exchange_set_width`` slots per lookup; the batch divides over the
    data axes like ``_exchange_meta``'s n_flat) and by the cold tier
    itself, and each staged block is fetched (stage) and returned
    (writeback) once.
    """
    from repro.embed import get_scheme
    from repro.tier.store import BLOCK_DEFAULT, tier_budget_mb, tier_split
    e = rcfg.embedding
    if e.budget is None:
        return {}
    scheme = get_scheme(e.kind)
    if scheme.family != "memory":
        return {}
    if rcfg.model == "xdeepfm":
        # mirrors launch/train._maybe_tier: the remap buffers ride in the
        # shared embedding buffers, so the second (linear) pool would see
        # the main pool's remap — xdeepfm always launches resident
        return {"tier": {"skipped": "dual memory pools stay resident"}}
    m = scheme.memory_slots(e)
    block = BLOCK_DEFAULT
    while m % block:
        block //= 2
    budget = tier_budget_mb()
    dp = [int(mesh.shape[a]) for a in ("pod", "data")
          if mesh is not None and a in mesh.axis_names]
    prod = int(np.prod(dp)) if dp else 1
    n_rows = B * recsys.lookups_per_example(rcfg) // prod
    # two pool leaves: the value pool + one optimizer-moment mirror (the
    # committed recsys archs all run a single-moment optimizer); staging
    # bound: one block per location element, like the launcher's measured
    # plan — set schemes read exchange_set_width slots per lookup
    n_loc = n_rows * max(scheme.exchange_set_width(e), 1)
    cap = min(n_loc, m // block)
    hot, cold = tier_split(m, budget, e.jdtype.itemsize, block,
                           n_leaves=2, stage_blocks=cap)
    staged = min(cold // block, cap)
    fetch = exl.tier_fetch_bytes(staged, block, n_leaves=2,
                                 itemsize=e.jdtype.itemsize)
    return {"tier": {"tier_budget_mb": budget, "hot_rows": int(hot),
                     "cold_rows": int(cold),
                     "host_fetch_bytes_per_step": int(fetch)}}


def _recsys_bundle(arch: ArchConfig, shape_id: str, mesh,
                   batch: int | None = None, smoke: bool = False) -> Bundle:
    t = dict(RECSYS_SHAPE_TABLE[shape_id])
    if batch is not None:
        t["batch"] = batch
    rcfg = arch.make_smoke() if smoke else arch.make_model(shape_id)
    rules = shd.recsys_rules()
    param_shapes = jax.eval_shape(lambda: recsys.init(jax.random.key(0), rcfg))
    param_sh = _shardings(mesh, param_shapes, rules)
    bufs, bufs_sh = _recsys_buffer_specs(rcfg, mesh)

    if t["kind"] == "train":
        B = t["batch"]
        optimizer = make_optimizer(arch)
        opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
        opt_sh = _shardings(mesh, opt_shapes, rules)
        batch, batch_sh = _recsys_batch_specs(rcfg, B, mesh)
        # sparse memory-pool gradients: the pool leaf arrives as a
        # SparseGrad over the K touched slots and the (dense-constructed,
        # sparse-aware) optimizer runs the O(K) lazy update; opt-state
        # structure and shardings are unchanged.  REPRO_SPARSE_GRADS=0
        # restores the dense oracle step bit-for-bit.  Gated by the traffic
        # model below: the sparse (indices, values) pair is replicated per
        # device, so at pod-scale global batches it can exceed the dense
        # slab update it replaces — then the dense path stays.
        use_sparse = (sparse_lib.sparse_enabled()
                      and sparse_lib.has_memory(param_shapes)
                      and _sparse_worthwhile(rcfg, B, mesh))

        def train_step(params, opt_state, buffers, batch):
            lf = lambda p: recsys.loss_fn(p, rcfg, batch, buffers)
            if use_sparse:
                (loss, m), grads = sparse_lib.sparse_value_and_grad(lf)(params)
            else:
                (loss, m), grads = jax.value_and_grad(
                    lf, has_aux=True)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = opt_lib.apply_updates(params, updates)
            return params, opt_state, loss

        return Bundle(
            arch.arch_id, shape_id, train_step,
            (param_shapes, opt_shapes, bufs, batch),
            (param_sh, opt_sh, bufs_sh, batch_sh),
            (param_sh, opt_sh, NamedSharding(mesh, P())),
            donate=(0, 1),
            meta={"kind": "train", "examples": B, "sparse_grads": use_sparse,
                  "embedding": rcfg.table.describe(),
                  **_sparse_meta(rcfg, B, mesh),
                  **_tier_meta(rcfg, B, mesh),
                  **_exchange_meta(
                      rcfg, B * recsys.lookups_per_example(rcfg), mesh)})

    if t["kind"] == "serve":
        B = t["batch"]
        batch, batch_sh = _recsys_batch_specs(rcfg, B, mesh)
        batch.pop("label"); batch_sh.pop("label")

        def serve_step(params, buffers, batch):
            return recsys.forward(params, rcfg, batch, buffers)

        out_sh = NamedSharding(mesh, _fit_dp(mesh, B))
        return Bundle(
            arch.arch_id, shape_id, serve_step,
            (param_shapes, bufs, batch),
            (param_sh, bufs_sh, batch_sh),
            out_sh, meta={"kind": "serve", "examples": B,
                          "embedding": rcfg.table.describe(),
                          **_exchange_meta(
                              rcfg, B * recsys.lookups_per_example(rcfg),
                              mesh)})

    # retrieval: one context vs n_candidates, chunked inside
    C = t["n_candidates"]
    batch, _ = _recsys_batch_specs(rcfg, 1, mesh)
    batch.pop("label")
    batch_sh = _rep(mesh, batch)
    cand = SDS((C,), jnp.int32)
    cand_sh = NamedSharding(mesh, P())
    chunk = int(t.get("chunk", 16384))

    def retrieval_step(params, buffers, batch, candidates):
        return recsys.retrieval(params, rcfg, batch, candidates, buffers,
                                chunk=chunk)

    return Bundle(
        arch.arch_id, shape_id, retrieval_step,
        (param_shapes, bufs, batch, cand),
        (param_sh, bufs_sh, batch_sh, cand_sh),
        NamedSharding(mesh, P()),
        meta={"kind": "retrieval", "examples": C,
              "embedding": rcfg.table.describe(),
              **_exchange_meta(rcfg, chunk, mesh)})


# ------------------------------------------------------------------------ GNN

def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _gnn_bundle(arch: ArchConfig, shape_id: str, mesh) -> Bundle:
    t = GNN_SHAPE_TABLE[shape_id]
    gcfg = arch.make_model(shape_id)
    ndev = int(np.prod(mesh.devices.shape))
    rules = shd.gnn_rules()
    optimizer = make_optimizer(arch)

    if t["kind"] == "batched_graphs":
        B, n, e = t["batch"], t["n_nodes"], t["n_edges"]
        N = B * n
        E = B * (2 * e + n)
        batch = {"features": SDS((N, t["d_feat"]), jnp.float32),
                 "src": SDS((E,), jnp.int32), "dst": SDS((E,), jnp.int32),
                 "graph_ids": SDS((N,), jnp.int32), "n_graphs": B,
                 "labels": SDS((B,), jnp.int32)}
    elif t["kind"] == "minibatch":
        b, (f1, f2) = t["batch_nodes"], t["fanout"]
        N = b + b * f1 + b * f1 * f2               # 169,984 for 1024/15-10
        E = b * f1 + b * f1 * f2 + N               # sampled edges + self loops
        batch = {"features": SDS((N, t["d_feat"]), jnp.float32),
                 "src": SDS((E,), jnp.int32), "dst": SDS((E,), jnp.int32),
                 "edge_mask": SDS((E,), jnp.bool_),
                 "labels": SDS((N,), jnp.int32),
                 "label_mask": SDS((N,), jnp.bool_)}
    else:  # full_graph
        N = _pad_to(t["n_nodes"], ndev)
        E = _pad_to(t["n_edges"] + t["n_nodes"], ndev)  # + self loops
        batch = {"features": SDS((N, t["d_feat"]), jnp.float32),
                 "src": SDS((E,), jnp.int32), "dst": SDS((E,), jnp.int32),
                 "edge_mask": SDS((E,), jnp.bool_),
                 "labels": SDS((N,), jnp.int32),
                 "label_mask": SDS((N,), jnp.bool_)}

    param_shapes = jax.eval_shape(lambda: gnn.init(jax.random.key(0), gcfg))
    param_sh = _shardings(mesh, param_shapes, rules)
    opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
    opt_sh = _shardings(mesh, opt_shapes, rules)

    def spec_for(k, v):
        if not hasattr(v, "shape") or v.shape == ():
            return NamedSharding(mesh, P())
        if k in ("src", "dst", "edge_mask"):
            tmpl = [[ALL, EP, "model", "data", None]]
        elif k in ("features", "labels", "label_mask", "graph_ids"):
            tmpl = [[DP, "data", None]] + [None] * (len(v.shape) - 1)
        else:
            tmpl = [None] * len(v.shape)
        return NamedSharding(mesh, shd.resolve_template(tmpl, v.shape, mesh))

    batch_sh = {k: spec_for(k, v) for k, v in batch.items()
                if hasattr(v, "shape")}
    batch = {k: v for k, v in batch.items() if hasattr(v, "shape")}
    if t["kind"] == "batched_graphs":
        fn_batch_static = {"n_graphs": t["batch"]}
    else:
        fn_batch_static = {}

    def train_step(params, opt_state, batch):
        full = dict(batch, **fn_batch_static)
        (loss, m), grads = jax.value_and_grad(
            lambda p: gnn.loss_fn(p, gcfg, full), has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        return params, opt_state, loss

    return Bundle(
        arch.arch_id, shape_id, train_step,
        (param_shapes, opt_shapes, batch),
        (param_sh, opt_sh, batch_sh),
        (param_sh, opt_sh, NamedSharding(mesh, P())),
        donate=(0, 1), meta={"kind": "train", "nodes": N, "edges": E})


def build_cell(arch_id: str, shape_id: str, mesh, *, batch: int | None = None,
               smoke: bool = False) -> Bundle:
    """``batch`` overrides the shape's examples per step and ``smoke`` takes
    the arch's reduced config (recsys cells only: the on-chip smoke run
    drives a cell at a per-chip batch, and rehearses it on the CPU)."""
    arch = get_config(arch_id)
    if shape_id not in arch.shapes:
        raise ValueError(f"{arch_id} does not define shape {shape_id}")
    if arch.family == "recsys":
        return _recsys_bundle(arch, shape_id, mesh, batch=batch, smoke=smoke)
    if batch is not None or smoke:
        raise ValueError("batch / smoke overrides apply to recsys cells only")
    if arch.family == "lm":
        return _lm_bundle(arch, shape_id, mesh)
    if arch.family == "gnn":
        return _gnn_bundle(arch, shape_id, mesh)
    raise ValueError(arch.family)
