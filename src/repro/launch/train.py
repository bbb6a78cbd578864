"""Training launcher: real data + the same step builders the dry-run lowers.

On hardware this runs under the production mesh; on this container it runs on
however many devices exist (1 CPU or N forced hosts).  The recsys family is
fully runnable end-to-end (synthetic CTR data with planted semantics); the LM
family runs at smoke scale with the bigram generator.

  PYTHONPATH=src python -m repro.launch.train --arch lma-dlrm-criteo \
      --steps 300 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 100
"""
from __future__ import annotations

import argparse
import os

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.embedding import get_scheme, make_buffers
from repro.core.signatures import build_signature_store, densify_store
from repro.data.lm_data import LMGenerator
from repro.data.metrics import StreamingEval
from repro.data.synthetic_ctr import CTRGenerator, CTRSpec, DINGenerator, DINSpec
from repro.dist import sharding as shd
from repro.launch.mesh import parse_mesh
from repro.launch.steps import store_rows
from repro.models import recsys, transformer
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sparse_lib
from repro.train.trainer import Trainer, TrainerConfig


def make_optimizer(arch, sparse_ok: bool = True):
    dense = {"adam": opt_lib.adam, "adagrad": opt_lib.adagrad,
             "adafactor": opt_lib.adafactor,
             "sgd": lambda lr: opt_lib.sgd(lr, momentum=0.9)}[
        arch.optimizer](arch.learning_rate)
    sparse = {"adam": sparse_lib.sparse_rowwise_adam,
              "adagrad": sparse_lib.sparse_adagrad,
              "sgd": lambda lr: sparse_lib.sparse_sgd(lr, momentum=0.9)}.get(
        arch.optimizer)
    if sparse_ok and sparse_lib.sparse_enabled() and sparse is not None:
        # the memory pool routes to the explicit sparse optimizer by path,
        # and with Adagrad (where the row-sparse update is the dense one)
        # so do the full baseline's tables; every other param keeps the
        # arch's dense transform untouched
        rules = [(r"(^|/)memory$", sparse(arch.learning_rate))]
        if arch.optimizer == "adagrad":
            rules.append((r"(^|/)table_\d+$", sparse_lib.sparse_adagrad(
                arch.learning_rate, tables=True)))
        return opt_lib.multi_transform(rules, default=dense)
    return dense


def row_tables(cfg) -> str | None:
    """The paths of a recsys model's row tables, which a mesh holds padded
    and lane-packed (``repro.dist.sharding.ROW_TABLES``): the full
    baseline's tables, whose rows are the embeddings; None for others."""
    return shd.ROW_TABLES if get_scheme(cfg.embedding.kind).table_rows \
        else None


def lookups_per_step(cfg, batch: int) -> int:
    """Embedding-row lookups one recsys step performs (the unit of the
    lookups_per_sec stat; per-example rule shared with steps.py's
    sparse-traffic model via models.recsys)."""
    return batch * recsys.lookups_per_example(cfg)


# compact pool leaves the dense optimizer keeps per pool slot, besides the
# value pool itself (adam: mu + nu; adagrad: acc; momentum-sgd: trace;
# adafactor: unfactored v — the pool is 1-D, under min_factor_dim)
MOMENT_LEAVES = {"adam": 2, "adagrad": 1, "sgd": 1, "adafactor": 1}


def _maybe_tier(cfg, arch, params, bufs, batch_fn, budget_mb):
    """Wrap a recsys setup in the tiered memory store when the pool exceeds
    the per-device HBM budget (``--tier-budget-mb`` / REPRO_TIER_BUDGET_MB).

    The budget bounds the pool's whole device footprint: the compact value
    pool, one same-sized mirror per optimizer moment, and each leaf's stage
    region.  Staging capacity is the per-step touched-block bound — one
    block per planned location element, measured from one planned batch —
    so the compact pool is genuinely budget-sized and staging can never
    overflow mid-run: an over-budget pool that would OOM resident fits
    after tiering.

    Returns ``(params, loss_fn, controller)``; untiered runs return
    ``(params, None, None)`` and keep the resident loss function.  Tiered
    params hold the *compact* pool; the controller's ``export_params``
    reconstructs the full pool for eval.  The tiered loss peels the
    per-step remap buffers out of the batch and merges them into the
    embedding buffers — the only change the model stack sees.
    """
    from repro.tier import (BLOCK_DEFAULT, TieredStore, TierController,
                            needs_tiering, split_batch, tier_split)
    e = cfg.embedding
    scheme = get_scheme(e.kind)
    if budget_mb is None or getattr(scheme, "family", None) != "memory":
        return params, None, None
    if cfg.model == "xdeepfm":
        # xdeepfm carries a second (linear) memory pool; the tier remap
        # buffers ride in the shared embedding buffers dict, so tiering the
        # main pool would corrupt the linear table's locations.
        print("tiering skipped: xdeepfm's dual memory pools stay resident")
        return params, None, None
    mem = np.asarray(params["embedding"]["memory"])
    m, itemsize = int(mem.shape[0]), mem.dtype.itemsize
    n_leaves = 1 + MOMENT_LEAVES[arch.optimizer]
    if not needs_tiering(m, itemsize, budget_mb, n_leaves=n_leaves):
        print(f"pool fits the {budget_mb} MB tier budget ({m} slots x "
              f"{n_leaves} leaves); untiered")
        return params, None, None
    block = BLOCK_DEFAULT
    while m % block:
        block //= 2
    offs = np.asarray(e.table_offsets()[:-1], np.int32)

    def plan_fn(batch):
        if cfg.model == "din":
            g = jnp.concatenate([jnp.ravel(batch["hist"]),
                                 jnp.ravel(batch["target"])])
        else:
            g = (batch["sparse"].astype(jnp.int32)
                 + jnp.asarray(offs)[None, :]).reshape(-1)
        return scheme.locations(e, bufs, g.astype(jnp.int32))

    # staging bound: a step touches at most one block per location ELEMENT
    # (a set scheme reads max_set slots per lookup, so rows alone undercount)
    # — the location shape is static across steps, so one planned batch
    # bounds them all, for any registered scheme
    cap = min(int(plan_fn(batch_fn(0)).size), m // block)
    hot_slots, cold_slots = tier_split(m, budget_mb, itemsize, block,
                                       n_leaves=n_leaves, stage_blocks=cap)
    cap = min(cap, cold_slots // block)
    if hot_slots <= 0:
        raise SystemExit(
            f"--tier-budget-mb {budget_mb}: the {n_leaves} compact pool "
            f"leaves' stage regions alone ({cap} blocks x {block} slots "
            f"each) exhaust the budget — raise the budget or shrink the "
            f"batch")
    store = TieredStore(mem, hot_slots, block=block, stage_blocks=cap)

    def tiered_loss(p, b, bufs):
        clean, tier = split_batch(b)
        return recsys.loss_fn(p, cfg, clean, {**bufs, **tier})

    params = dict(params, embedding=dict(
        params["embedding"], memory=store.initial_compact()))
    dev_mb = n_leaves * store.compact_slots * itemsize / 2**20
    print(f"tiered memory pool: {m} slots -> {store.hot_slots} hot + "
          f"{m - store.hot_slots} cold, stage {store.stage_blocks} blocks "
          f"(block {block}; {n_leaves} leaves x {store.compact_slots} slots "
          f"= {dev_mb:.0f} MB on device, budget {budget_mb} MB)")
    return params, tiered_loss, TierController(store, batch_fn, plan_fn)


def recsys_setup(arch, cfg, n_s: int, batch: int):
    """Data generator, embedding buffers (the D' store from ``n_s`` rows
    for lma), ``batch_fn(step)`` and ``loss_fn(params, batch, bufs)`` —
    the buffers reach the jitted step as an argument, never a closure."""
    e = cfg.embedding
    if cfg.model == "din":
        gen = DINGenerator(DINSpec(n_items=e.vocab_sizes[0], hist_len=max(
            cfg.hist_len, 8), n_clusters=50, seed=0))
    else:
        spec = CTRSpec(n_fields=cfg.n_fields, n_dense=cfg.n_dense,
                       vocab_sizes=e.vocab_sizes, seed=0)
        gen = CTRGenerator(spec)
    # data preparation keyed on the scheme's declared buffer source, so a
    # registered scheme's buffers build here without a kind check
    scheme = get_scheme(e.kind)
    bufs = {}
    if scheme.buffer_source == "signatures":
        print(f"building D' ({n_s} rows)...")
        store = build_signature_store(gen.rows_for_signatures(n_s),
                                      e.total_vocab, max_per_value=e.lma.max_set)
        # rows padded like the dry-run's buffer specs, so one store serves
        # the single-device step and the 'model'-sharded one
        bufs = make_buffers(e, densify_store(
            store, e.lma.max_set, n_rows=store_rows(e.total_vocab)))
    elif scheme.buffer_source == "id_counts":
        print(f"counting observed ids ({n_s} rows)...")
        counts = np.zeros(e.total_vocab, np.int64)
        for row in gen.rows_for_signatures(n_s):
            np.add.at(counts, np.asarray(row, np.int64), 1)
        bufs = make_buffers(e, counts)

    def batch_fn(step):
        return {k: jnp.asarray(v) for k, v in gen.batch(batch, step).items()}

    return gen, bufs, batch_fn, (
        lambda p, b, bufs: recsys.loss_fn(p, cfg, b, bufs))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lma-dlrm-criteo")
    ap.add_argument("--embedding-kind", default=None,
                    help="override the arch's embedding scheme (any "
                         "registered kind, e.g. freq); recsys archs only")
    ap.add_argument("--exchange", default=None,
                    choices=["psum", "ring", "all_to_all", "auto"],
                    help="pin the sharded-lookup/update exchange strategy "
                         "(default: REPRO_DIST_EXCHANGE or the "
                         "resolve_exchange cost model); only observable "
                         "when a distribution mesh is installed")
    ap.add_argument("--mesh", default=None,
                    help="train on a ('data', 'model') mesh of "
                         "<data>x<model> devices, e.g. 1x4: parameters "
                         "placed by repro.dist.sharding.recsys_rules (the "
                         "full baseline's tables row-sharded over 'model'), "
                         "the embedding lookups on their sharded paths; "
                         "recsys archs only")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (required for LM archs here)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--n-signatures", type=int, default=10_000)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec, e.g. "
                         "'nan_grad@17,rot_row@40:8,slow_rank@55:0.5' "
                         "(see repro.resilience.faults; also REPRO_FAULTS)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault injector's corruption bits")
    ap.add_argument("--tier-budget-mb", type=float, default=None,
                    help="per-device HBM budget for the embedding memory "
                         "pool; a pool that exceeds it trains through the "
                         "tiered store (HBM-hot / host-cold, repro.tier) "
                         "bit-identically to the resident run (also "
                         "REPRO_TIER_BUDGET_MB; recsys archs only)")
    ap.add_argument("--no-guard", action="store_true",
                    help="disable the in-jit non-finite step guard "
                         "(also REPRO_GUARD_STEP=0)")
    ap.add_argument("--ckpt-delta", action="store_true",
                    default=os.environ.get("REPRO_CKPT_DELTA", "").lower()
                    in ("1", "true", "on", "yes"),
                    help="incremental checkpoints: persist only the pool "
                         "chunks dirtied since the last durable step "
                         "(SparseGrad indices / tier writeback feed the "
                         "dirty set; also REPRO_CKPT_DELTA=1)")
    ap.add_argument("--ckpt-compact-every", type=int, default=8,
                    help="delta-chain length before forcing a full base "
                         "checkpoint (bounds restore replay cost)")
    ap.add_argument("--profile-dir", default=None,
                    help="write a profiler trace of the training loop "
                         "here: the trainer's train.* host spans and the "
                         "step's named scopes (README, 'Tracing')")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    print(f"compile cache: {setup_compile_cache()}")

    if args.exchange is not None:
        from repro.dist import exchange as exl
        exl.FORCED = None if args.exchange == "auto" else args.exchange

    arch = get_config(args.arch)
    kind_kw = {} if args.embedding_kind is None \
        else {"embedding_kind": args.embedding_kind}
    cfg = arch.make_smoke(**kind_kw) if (args.smoke or arch.family == "lm") \
        else arch.make_model(None, **kind_kw)

    tier_ctrl = mesh = None
    if args.mesh is not None and arch.family != "recsys":
        raise SystemExit("--mesh trains recsys archs only")
    if arch.family == "recsys":
        gen, bufs, batch_fn, loss_fn = recsys_setup(
            arch, cfg, args.n_signatures, args.batch)
        if args.mesh is not None:
            mesh = parse_mesh(args.mesh)
            # the host draws the tables whole (8.6 GB at Criteo width, more
            # than one chip would hold beside the rest); the Trainer hands
            # each device its own shard
            with jax.default_device(jax.devices("cpu")[0]):
                params = recsys.init(jax.random.key(0), cfg)
            bufs = shd.place(bufs, mesh, shd.buffer_rules())
            print(f"mesh {dict(mesh.shape)}")
        else:
            params = recsys.init(jax.random.key(0), cfg)
        from repro.tier import tier_budget_mb
        budget_mb = (args.tier_budget_mb if args.tier_budget_mb is not None
                     else tier_budget_mb())
        if mesh is None:
            params, tiered_loss, tier_ctrl = _maybe_tier(
                cfg, arch, params, bufs, batch_fn, budget_mb)
        if tier_ctrl is not None:
            loss_fn = tiered_loss
    elif arch.family == "lm":
        gen = LMGenerator(cfg.vocab_size, seed=0)

        def batch_fn(step):
            b = gen.batch(min(args.batch, 16), 64, step)
            return {k: jnp.asarray(v) for k, v in b.items()}

        def loss_fn(p, b, bufs):
            return transformer.loss_fn(p, cfg, b["tokens"], b["labels"])

        params = transformer.init(jax.random.key(0), cfg)
        bufs = {}
    else:
        raise SystemExit(f"use examples/ for family {arch.family}")

    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    print(f"{args.arch}: {n_params:,} parameters on {len(jax.devices())} "
          f"device(s)")
    lps = (lookups_per_step(cfg, args.batch) if arch.family == "recsys"
           else min(args.batch, 16) * 64)
    injector = None
    if args.faults:
        from repro.resilience.faults import FaultInjector
        injector = FaultInjector(args.faults, seed=args.fault_seed)
        print(f"fault injection armed: {args.faults} (seed {args.fault_seed})")
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=100, log_every=max(args.steps // 10, 1),
                      lookups_per_step=lps,
                      ckpt_delta=args.ckpt_delta,
                      ckpt_compact_every=args.ckpt_compact_every,
                      guard_step=False if args.no_guard else None),
        # a tiered pool updates densely: the compact pool is already only
        # the budgeted hot+stage slots, and the sparse pipeline's explicit
        # per-pool optimizer keeps its moments in a state shape the tier
        # migration cannot mirror (the full-pool layout)
        loss_fn, params, make_optimizer(arch, sparse_ok=tier_ctrl is None),
        batch_fn, faults=injector,
        sparse_grads=False if tier_ctrl is not None else None,
        tier=tier_ctrl, loss_args=(bufs,), mesh=mesh,
        rules=shd.recsys_rules() if mesh is not None else (),
        row_tables=row_tables(cfg) if mesh is not None else None)
    if trainer.sparse_grads:
        from repro.dist import exchange as exl
        print("sparse memory-pool updates ON (REPRO_SPARSE_GRADS=0 for the "
              "dense oracle; exchange strategy "
              f"{exl.FORCED or 'auto'})")
    trainer.install_signal_handlers()
    if args.profile_dir:
        # the compile cache's key leaves op metadata out by default: a step
        # compiled before a scope existed would be served with old names
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        jax.profiler.start_trace(args.profile_dir)
    try:
        out = trainer.fit()
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
    print(f"done: {out}")
    if trainer.health.any_faults():
        print(f"health: {trainer.health.summary()}")

    if arch.family == "recsys":
        ev = StreamingEval()
        # a tiered run evaluates through the reconstructed full pool
        # (bit-exact export) — eval batches are unplanned, so they may
        # touch blocks the training staging never covered
        eval_params = (tier_ctrl.export_params(trainer.params)
                       if tier_ctrl is not None else trainer.params)
        if tier_ctrl is not None:
            print(f"tier: {trainer.tier.stats()}")
        fwd = jax.jit(lambda p, b, bufs: recsys.forward(p, cfg, b, bufs))
        for i in range(args.eval_batches):
            b = gen.batch(2048, 700_000 + i)
            jb = {k: jnp.asarray(v) for k, v in b.items() if k != "label"}
            with trainer.mesh_context():
                ev.add(b["label"], np.asarray(fwd(eval_params, jb, bufs)))
        print(f"eval: {ev.compute()}")


if __name__ == "__main__":
    main()
