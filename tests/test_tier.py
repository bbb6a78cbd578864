"""repro.tier — the HBM-hot / host-cold tiered memory store.

The contract under test is *bit-exactness*: an over-budget pool trained
through the tiered store (async staged cold blocks, EMA re-tiering, host
writeback) must be indistinguishable — values AND optimizer moments — from
the same run with the pool fully resident.  The tests build up that claim:
remap identity -> store round-trip -> re-tier migration -> the public
embed path -> a 25-step Trainer run with re-tiering against the resident
oracle.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.embed import EmbeddingTable, get_scheme
from repro.embed import backends as bke
from repro.embed.config import EmbeddingConfig
from repro.optim import optimizers as opt_lib
from repro.tier import (BLOCK_DEFAULT, TieredStore, TierController,
                        budget_slots, needs_tiering, pool_leaf_paths,
                        remap_locations, split_batch, tier_split)
from repro.train.trainer import Trainer, TrainerConfig


# ------------------------------------------------------------ budget helpers

def test_budget_slots_floors_to_blocks():
    # 1 MB / 4 B = 262144 slots, already block-aligned
    assert budget_slots(1.0, itemsize=4, block=512) == 262144
    # a budget that lands mid-block is floored, never rounded up
    assert budget_slots(0.001, itemsize=4, block=512) == 0
    assert budget_slots(0.01, itemsize=4, block=512) == 2560  # 2621 -> 5 blocks


def test_tier_split_rules():
    assert tier_split(4096, None) == (4096, 0)            # no budget: all hot
    assert tier_split(4096, 1000.0) == (4096, 0)          # pool fits
    hot, cold = tier_split(1 << 20, 1.0, itemsize=4)
    assert hot == 262144 and cold == (1 << 20) - 262144
    assert hot % BLOCK_DEFAULT == 0


def test_tier_split_budget_covers_leaves_and_staging():
    """The budget bounds the WHOLE device footprint: each of the n_leaves
    compact leaves gets budget/n_leaves slots, and the stage region is
    carved out of that before the hot slab."""
    # 1 MB / 4 B = 262144 slots; two leaves -> 131072 each; 16 stage blocks
    # (8192 slots) leave 122880 hot
    hot, cold = tier_split(1 << 20, 1.0, itemsize=4, n_leaves=2,
                           stage_blocks=16)
    assert hot == 131072 - 16 * BLOCK_DEFAULT
    assert hot + cold == 1 << 20 and hot % BLOCK_DEFAULT == 0
    # a pool whose full n_leaves footprint fits stays all-hot, no staging
    assert tier_split(4096, 1.0, n_leaves=2, stage_blocks=16) == (4096, 0)
    # staging can exhaust the per-leaf budget: hot collapses to 0, loudly
    # checkable by the caller (the launcher refuses to run that config)
    assert tier_split(1 << 20, 1.0, itemsize=4, n_leaves=2,
                      stage_blocks=10_000)[0] == 0


def test_needs_tiering():
    assert not needs_tiering(4096, budget_mb=1000.0)
    assert needs_tiering(1 << 20, budget_mb=1.0)
    assert not needs_tiering(1 << 20, budget_mb=None)     # env unset: untiered
    # with the moment mirrors counted, half the budget per leaf
    assert needs_tiering(200_000, budget_mb=1.0, n_leaves=2)
    assert not needs_tiering(200_000, budget_mb=1.0, n_leaves=1)


# ---------------------------------------------------------- remap identity

def test_remap_locations_bit_identity():
    """take(compact, remap(loc)) == take(full, loc) for every location whose
    block is hot or staged — the invariant every tiered lookup rests on."""
    rng = np.random.default_rng(0)
    block, n_blocks = 64, 32
    m = block * n_blocks
    full = rng.normal(size=m).astype(np.float32)
    hot_ids = np.sort(rng.choice(n_blocks, 10, replace=False)).astype(np.int32)
    rest = np.setdiff1d(np.arange(n_blocks), hot_ids)
    staged = np.sort(rng.choice(rest, 6, replace=False)).astype(np.int32)
    # stage region padded with the n_blocks sentinel, like the store emits
    stage_ids = np.concatenate([staged, np.full(2, n_blocks, np.int32)])
    compact = np.concatenate([
        full.reshape(n_blocks, block)[hot_ids].reshape(-1),
        full.reshape(n_blocks, block)[staged].reshape(-1),
        np.zeros(2 * block, np.float32)])
    covered = np.concatenate([hot_ids, staged])
    loc = (rng.choice(covered, (37, 5)) * block
           + rng.integers(0, block, (37, 5))).astype(np.int32)
    got = jnp.take(jnp.asarray(compact),
                   remap_locations(jnp.asarray(loc), jnp.asarray(hot_ids),
                                   jnp.asarray(stage_ids), block))
    np.testing.assert_array_equal(np.asarray(got), full[loc])


def test_remap_locations_empty_tiers():
    loc = jnp.arange(8, dtype=jnp.int32)
    # all-hot pool (no stage): identity when hot_ids = arange
    got = remap_locations(loc, jnp.arange(4, dtype=jnp.int32),
                          jnp.full((1,), 4, jnp.int32), 2)
    np.testing.assert_array_equal(np.asarray(got), np.arange(8))


# ------------------------------------------------------------ store protocol

def _store(m=2048, block=128, hot_slots=512, seed=0, **kw):
    rng = np.random.default_rng(seed)
    mem = rng.normal(size=m).astype(np.float32)
    # full-cold staging, passed EXPLICITLY: the small-pool testing posture
    # (a defaulted stage capacity warns — it erases the HBM savings)
    kw.setdefault("stage_blocks", (m - hot_slots) // block)
    return mem, TieredStore(mem, hot_slots, block=block, **kw)


def test_defaulted_stage_capacity_warns():
    rng = np.random.default_rng(0)
    mem = rng.normal(size=2048).astype(np.float32)
    with pytest.warns(UserWarning, match="saves no HBM"):
        TieredStore(mem, 512, block=128)
    # explicit capacity (or an all-hot store) stays quiet
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        TieredStore(mem, 512, block=128, stage_blocks=4)
        TieredStore(mem, 2048, block=128)


def test_stage_install_writeback_round_trip():
    mem, st = _store()
    tree = {"memory": st.initial_compact()}
    # the full pool reconstructs the original bits before any step
    np.testing.assert_array_equal(st.full_pool(tree["memory"]), mem)
    blocks = np.array([0, 5, 9, 13])            # mix of hot (0..3) and cold
    st.stage(blocks)
    tree = st.install(tree)
    assert tree["memory"].shape == (st.compact_slots,)
    np.testing.assert_array_equal(st.full_pool(tree["memory"]), mem)
    # a training step bumps hot row 7 and a staged cold row
    upd = np.asarray(tree["memory"]).copy()
    upd[7] += 1.0
    upd[st.hot_slots + 3] += 2.0                # block 9's 4th slot... row 3
    tree = {"memory": jnp.asarray(upd)}
    st.writeback(tree)
    full = st.full_pool(tree["memory"])
    assert full[7] == mem[7] + 1.0
    # staged ids sorted -> [5, 9, 13]; slot 3 of the stage region is in
    # block 5 (stage row 0 covers slots 0..127)
    assert full[5 * 128 + 3] == mem[5 * 128 + 3] + 2.0


def test_stage_overflow_raises():
    _, st = _store(stage_blocks=2)
    with pytest.raises(ValueError, match="stage capacity"):
        st.stage(np.array([5, 7, 9]))           # 3 cold blocks, capacity 2


def test_register_leaf_rejects_nonuniform():
    _, st = _store()
    with pytest.raises(ValueError, match="uniform"):
        st.register_leaf("opt", jnp.arange(st.compact_slots, dtype=jnp.float32))


def test_retier_migrates_bits_and_moments():
    mem, st = _store(m=2048, block=128, hot_slots=512)
    acc0 = 0.1
    tree = {"memory": st.initial_compact(),
            "opt:acc": jnp.full(st.compact_slots, acc0, jnp.float32)}
    st.writeback(tree)                          # registers the moment leaf
    # make blocks 12..15 the hottest; incumbents 0..3 never observed
    st.observe(np.array([12, 13, 14, 15]), np.array([100, 90, 80, 70]))
    tree, info = st.retier(tree)
    assert info == {"promoted": 4, "demoted": 4}
    assert st.stats["promoted"] == 4
    np.testing.assert_array_equal(st.hot_ids, [12, 13, 14, 15])
    # migration is bit-exact for both leaves: the full pools are unchanged
    np.testing.assert_array_equal(st.full_pool(tree["memory"]), mem)
    np.testing.assert_array_equal(st.full_pool(tree["opt:acc"], "opt:acc"),
                                  np.full(2048, acc0, np.float32))
    # the new hot slab holds blocks 12..15's rows verbatim
    np.testing.assert_array_equal(
        np.asarray(tree["memory"][: st.hot_slots]), mem[12 * 128: 16 * 128])


def test_retier_hysteresis_and_max_swaps():
    _, st = _store(m=2048, block=128, hot_slots=512)
    tree = {"memory": st.initial_compact()}
    st.observe(np.arange(16), np.linspace(10, 12, 16))   # mild gradient
    # a 2x hysteresis bar: no challenger beats an incumbent by 2x
    tree, info = st.retier(tree, hysteresis=2.0)
    assert info == {"promoted": 0, "demoted": 0}
    np.testing.assert_array_equal(st.hot_ids, np.arange(4))
    # without the bar the top-4 swap in, capped at 1 swap
    tree, info = st.retier(tree, max_swaps=1, hysteresis=1.0)
    assert info == {"promoted": 1, "demoted": 1}


def test_sanitize_cold_quarantines_only_cold():
    mem, st = _store(m=2048, block=128, hot_slots=512)
    st._host["memory"][10, 5] = np.nan          # cold block: quarantined
    st._host["memory"][1, 5] = np.nan           # hot block: device-owned,
    n = st.sanitize_cold()                      # the in-run scan covers it
    assert n >= 1 and st.stats["quarantined_cold_chunks"] == n
    assert not np.isnan(st._host["memory"][10]).any()
    assert np.isnan(st._host["memory"][1, 5])


def test_counts_seed_hot_set():
    rng = np.random.default_rng(3)
    mem = rng.normal(size=2048).astype(np.float32)
    counts = np.zeros(16)
    counts[[3, 8, 11, 14]] = [50, 40, 30, 20]
    st = TieredStore(mem, 512, block=128, stage_blocks=12, counts=counts)
    np.testing.assert_array_equal(st.hot_ids, [3, 8, 11, 14])


# -------------------------------------------------- public embed path

def _embed_cfg():
    return EmbeddingConfig(kind="hashed_elem", vocab_sizes=(1000, 500),
                           dim=16, budget=4096)


def test_tiered_embed_fields_bit_exact():
    """The public EmbeddingTable path: compact pool + remap buffers in the
    embedding buffers -> bit-identical to the resident lookup."""
    cfg = _embed_cfg()
    table = EmbeddingTable(cfg)
    scheme = get_scheme(cfg.kind)
    bufs = table.make_buffers()
    params = table.init(jax.random.key(1))
    rng = np.random.default_rng(2)
    ids = jnp.asarray(np.stack([rng.integers(0, 1000, 64),
                                rng.integers(0, 500, 64)], 1).astype(np.int32))
    want = table.embed_fields(params, bufs, ids)

    st = TieredStore(np.asarray(params["memory"]), 1024, block=128,
                     stage_blocks=24)
    offs = np.asarray(cfg.table_offsets()[:-1], np.int32)
    gids = (np.asarray(ids) + offs[None, :]).reshape(-1)
    loc = scheme.locations(cfg, bufs, jnp.asarray(gids))
    st.stage(st.touched_blocks(loc)[0])
    tree = st.install({"memory": st.initial_compact()})
    tbufs = {**bufs, **st.batch_tier_buffers()}
    assert bke.resolve_backend(cfg, tree, scheme, tbufs) is bke.TIERED
    got = table.embed_fields(tree, tbufs, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------- end-to-end training parity

def test_tiered_training_parity_vs_resident_oracle():
    """The acceptance test: 25 adagrad steps over a 4x over-budget pool,
    re-tiering every 4 steps, must leave the (reconstructed) full pool AND
    the optimizer accumulator bit-identical to the fully-resident run —
    and the fit result carries the guard/exchange fields (PR satellite)
    plus the tier throughput stats."""
    cfg = _embed_cfg()
    table = EmbeddingTable(cfg)
    scheme = get_scheme(cfg.kind)
    bufs = table.make_buffers()
    params0 = {"embedding": table.init(jax.random.key(1))}
    m = int(params0["embedding"]["memory"].shape[0])
    offs = np.asarray(cfg.table_offsets()[:-1], np.int32)

    def raw_batch(step):
        r = np.random.default_rng(step)
        return {"ids": jnp.asarray(np.stack(
                    [r.integers(0, 1000, 64), r.integers(0, 500, 64)],
                    1).astype(np.int32)),
                "y": jnp.asarray(r.normal(size=(64, 2, 16)).astype(np.float32))}

    def make_loss(base_bufs):
        def loss(p, b):
            batch, tier = split_batch(b)
            e = table.embed_fields(p["embedding"], {**base_bufs, **tier},
                                   batch["ids"])
            l = jnp.mean((e - batch["y"]) ** 2)
            return l, {"l": l}
        return loss

    def fit(tier_ctrl):
        # real copies: the trainer donates params, and both fits (plus the
        # tier store's host mirror) start from the same initial pool
        params = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                        params0)
        if tier_ctrl is not None:
            params = {"embedding": dict(
                params["embedding"],
                memory=tier_ctrl.store.initial_compact())}
        tr = Trainer(TrainerConfig(total_steps=25, log_every=0),
                     make_loss(bufs), params, opt_lib.adagrad(0.1),
                     raw_batch, sparse_grads=False, tier=tier_ctrl)
        out = tr.fit(log=lambda s: None)
        return tr, out

    oracle, _ = fit(None)

    st = TieredStore(np.asarray(params0["embedding"]["memory"]), 1024,
                     block=128, stage_blocks=24)

    def plan_fn(batch):
        gids = (np.asarray(batch["ids"]) + offs[None, :]).reshape(-1)
        return scheme.locations(cfg, bufs, jnp.asarray(gids))

    ctrl = TierController(st, raw_batch, plan_fn, retier_every=4)
    tiered, out = fit(ctrl)
    assert st.stats["promoted"] > 0, "re-tiering never fired"

    # values: reconstructed full pool == resident pool, bitwise
    full = np.asarray(
        ctrl.export_params(tiered.params)["embedding"]["memory"])
    np.testing.assert_array_equal(
        full, np.asarray(oracle.params["embedding"]["memory"]))

    # moments: the adagrad accumulator migrated bit-exactly too
    (_, acc_c), = pool_leaf_paths(tiered.opt_state, st.compact_slots)
    (_, acc_o), = pool_leaf_paths(oracle.opt_state, m)
    name, = [k for k in st._host if k != "memory"]
    np.testing.assert_array_equal(st.full_pool(acc_c, name),
                                  np.asarray(acc_o))

    # result-dict satellite: guard/exchange fields + tier throughput stats
    for k in ("guard_enabled", "exchange", "tier_hot_rows", "tier_cold_rows",
              "tier_staged_blocks_per_step", "tier_host_fetch_bytes_per_step",
              "tier_promoted", "tier_demoted"):
        assert k in out, k
    assert out["tier_hot_rows"] == 1024
    assert out["tier_cold_rows"] == m - 1024
    assert out["exchange"] == "auto"
    assert out["tier_host_fetch_bytes_per_step"] > 0


def test_launcher_maybe_tier_is_genuinely_budget_bounded():
    """The launcher path must hand the store a batch-derived staging bound:
    the compact device pool (every leaf, stage region included) fits the
    --tier-budget-mb budget, so an over-budget pool actually saves HBM —
    and a budget that staging alone exhausts is refused, never silently
    over-allocated."""
    from repro.configs.base import get_config
    from repro.launch.train import MOMENT_LEAVES, _maybe_tier, recsys_setup
    from repro.models import recsys

    arch = get_config("din")
    cfg = arch.make_model(None)
    gen, bufs, batch_fn, _ = recsys_setup(arch, cfg, 300, 2)
    params = recsys.init(jax.random.key(0), cfg)
    m = int(params["embedding"]["memory"].shape[0])
    budget_mb = 32.0
    n_leaves = 1 + MOMENT_LEAVES[arch.optimizer]
    assert m * n_leaves * 4 > budget_mb * 2**20, "pool must be over budget"
    tiered, loss, ctrl = _maybe_tier(cfg, arch, params, bufs, batch_fn,
                                     budget_mb)
    assert ctrl is not None and loss is not None
    st = ctrl.store
    assert st.compact_slots < m, "compact pool must be smaller than the pool"
    assert st.stage_blocks < st.cold_blocks, "staging must be bounded"
    dev_bytes = n_leaves * st.compact_slots * 4
    assert dev_bytes <= budget_mb * 2**20, (dev_bytes, budget_mb * 2**20)
    assert tiered["embedding"]["memory"].shape == (st.compact_slots,)
    # one controller step stays within the staging bound it derived
    p, o, info = ctrl.pre_step(0, tiered, {})
    assert 0 < info["staged"] <= st.stage_blocks

    # a budget the stage regions alone exhaust is refused loudly: the
    # criteo pool (208 blocks) is smaller than one step's planned working
    # set, so no budget below its resident size can tier it
    arch_c = get_config("lma-dlrm-criteo")
    cfg_c = arch_c.make_model(None)
    gen, bufs_c, batch_fn_c, _ = recsys_setup(arch_c, cfg_c, 300, 4)
    params_c = recsys.init(jax.random.key(0), cfg_c)
    with pytest.raises(SystemExit, match="stage regions alone"):
        _maybe_tier(cfg_c, arch_c, params_c, bufs_c, batch_fn_c, 0.5)


def test_controller_on_restore_drops_staged_rows():
    cfg = _embed_cfg()
    table = EmbeddingTable(cfg)
    st = TieredStore(np.asarray(table.init(jax.random.key(1))["memory"]),
                     1024, block=128, stage_blocks=24)
    st.stage(np.array([9, 10]))
    tree = st.install({"memory": st.initial_compact()})
    ctrl = TierController(st, lambda s: {}, lambda b: None)
    assert st._staged_ids is not None and st._staged_ids.size == 2
    ctrl.on_restore()
    assert st._staged_ids is None
    st.writeback(tree)                          # must be a clean no-op
    assert st.stats["writeback_bytes"] == 0
