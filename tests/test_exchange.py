"""The repro.dist.exchange strategy layer.

Fast tests: the ``resolve_exchange`` / ``sparse_worthwhile`` cost-model
tables (pure functions of mesh shape + sizes — meshes are faked with a
``shape`` namespace, no devices needed) and strategy eligibility.

Slow tests (subprocess, 8 forced host devices, 2x4 ('data','model') mesh):

  * forward parity of ring and all_to_all against the psum oracle — and the
    single-device lookup — for ALL registered schemes through the public
    ``EmbeddingTable.embed`` API, plus the standalone ``sharded_set_lookup``
    driver (row-sharded integer tables, exact under every strategy);
  * 10-step sparse-training parity (adagrad) for the memory-family schemes
    under all three forced strategies — psum (replicated updates),
    all_to_all (owner-partial updates), and ring (ring lookup backward,
    psum update fallback) — against the single-device dense oracle.
"""
from __future__ import annotations

import os
import subprocess
import sys
import types

import pytest

from repro.dist import exchange as exl


def fake_mesh(**axes):
    return types.SimpleNamespace(shape=dict(axes))


MESH_2x4 = fake_mesh(data=2, model=4)
MESH_16x16 = fake_mesh(data=16, model=16)


# ------------------------------------------------------------ resolve table

def test_resolve_psum_without_model_axis():
    assert exl.resolve_exchange(None) is exl.PSUM
    assert exl.resolve_exchange(fake_mesh(data=8), B=1024, d=32) is exl.PSUM


def test_resolve_psum_on_unknown_or_indivisible_batch():
    assert exl.resolve_exchange(MESH_2x4) is exl.PSUM
    assert exl.resolve_exchange(MESH_2x4, B=33, d=16, m=4096) is exl.PSUM


def test_resolve_forced_overrides_model():
    old = exl.FORCED
    try:
        exl.FORCED = "ring"
        assert exl.resolve_exchange(MESH_2x4, B=4096, d=32) is exl.RING
        exl.FORCED = "all_to_all"
        assert exl.resolve_exchange(MESH_2x4, B=4096, d=32) is exl.ALL_TO_ALL
    finally:
        exl.FORCED = old


def test_resolve_fused_slab_prefers_psum_chunked_otherwise():
    """The cost model's fused term: a slab under the engine's VMEM budget
    hashes in-VMEM (location bytes ~0) and psum wins; the production-scale
    pool (135M slots -> 34 MiB/device at 16 ranks, over the 16 MiB gate)
    pays the full location round-trip and a chunked strategy takes over."""
    small = exl.resolve_exchange(MESH_2x4, B=4096, d=32, m=1 << 21)
    assert small is exl.PSUM
    big = exl.resolve_exchange(MESH_16x16, B=4096, d=32, m=135_266_304)
    assert big in (exl.RING, exl.ALL_TO_ALL)


def test_lookup_cost_alloc_term_moves_the_choice():
    """Expensive allocators (alloc_row up, e.g. LMA's set reconstruction +
    minhash) favor the chunked strategies; free allocators favor psum."""
    c_free = exl.lookup_cost(4, 4096, 32, alloc_row=0.0)
    assert min(c_free, key=c_free.get) == "psum"
    c_lma = exl.lookup_cost(4, 4096, 32,
                            alloc_row=exl.alloc_bytes_per_row(32, 32))
    assert min(c_lma, key=c_lma.get) != "psum"
    # chunked strategies cut the alloc term by n_model, psum pays it whole
    delta = exl.alloc_bytes_per_row(32, 32) * 4096
    assert c_lma["psum"] - c_free["psum"] == pytest.approx(delta)
    assert c_lma["ring"] - c_free["ring"] == pytest.approx(delta / 4)
    # the fused-SLAB discount is psum-only: ring/all_to_all run the chunked
    # engine instead, priced by the separate ``fused_chunk`` flag — the
    # slab flag must not move their entries
    c_def = exl.lookup_cost(4, 4096, 32)
    c_fus = exl.lookup_cost(4, 4096, 32, fused=True)
    assert c_fus["psum"] == pytest.approx(c_def["psum"] - 8 * 32 * 4096)
    assert c_fus["ring"] == pytest.approx(c_def["ring"])
    assert c_fus["all_to_all"] == pytest.approx(c_def["all_to_all"])


def test_lookup_cost_fused_chunk_discount_is_chunked_only():
    """The chunk-level discount mirrors the slab one with the roles swapped:
    ``fused_chunk`` removes the [d] location-row term from ring/all_to_all's
    per-chunk alloc share and leaves psum untouched — each strategy's
    discount rides its own engine form and its own gate."""
    d, n = 32, 4096
    loc = 8 * d * n
    c_def = exl.lookup_cost(4, n, d)
    c_fc = exl.lookup_cost(4, n, d, fused_chunk=True)
    assert c_fc["psum"] == pytest.approx(c_def["psum"])
    assert c_fc["ring"] == pytest.approx(c_def["ring"] - loc / 4)
    assert c_fc["all_to_all"] == pytest.approx(c_def["all_to_all"] - loc / 4)
    # LMA's set-reconstruction exchange (alloc_row excess over 8d) is a
    # collective and survives the in-VMEM hash discount
    row = exl.alloc_bytes_per_row(d, 32)
    c_lma = exl.lookup_cost(4, n, d, alloc_row=row, fused_chunk=True)
    assert c_lma["ring"] == pytest.approx(c_fc["ring"] + 8 * 32 * n / 4)
    assert c_lma["all_to_all"] == pytest.approx(
        c_fc["all_to_all"] + 8 * 32 * n / 4)
    # both discounts together: psum's pure-collective 2(P-1)/P x row still
    # undercuts ring's overlap+homing and all_to_all's three barriers, so
    # in-budget slabs keep resolving to psum
    c_both = exl.lookup_cost(4, n, d, fused=True, fused_chunk=True)
    assert min(c_both, key=c_both.get) == "psum"


def test_chunk_gate_strictly_weaker_than_slab_gate():
    """``fused_chunk_eligible`` admits every slab the whole-slab gate does
    (one block) plus over-gate slabs some power-of-two tiling fits — the
    135M-slot production shape chunk-fuses where psum's form cannot."""
    m_big = 135_266_304                  # 34 MiB/device at 16 ranks
    assert not exl.fused_slab_eligible(m_big, 16)
    assert exl.fused_chunk_eligible(m_big, 16)
    assert exl.fused_slab_eligible(1 << 21, 4)
    assert exl.fused_chunk_eligible(1 << 21, 4)
    # indivisible pools cannot chunk at all
    assert not exl.fused_chunk_eligible(m_big + 1, 16)
    assert not exl.fused_chunk_eligible(m_big, 1)


def test_resolve_clamps_caller_asserted_fused_chunk_flag():
    """Like the psum flag, an explicit ``fused_chunk=True`` routes through
    its gate: a pool the 'model' axis does not divide (or whose chunks
    cannot fit the budget) pays full location bytes — asserted and honest
    resolutions coincide, so modeled dispatch can never promise an engine
    form the drivers would refuse to run."""
    m_odd = 135_266_304 + 1
    assert not exl.fused_chunk_eligible(m_odd, 16)
    honest = exl.resolve_exchange(MESH_16x16, B=4096, d=32, m=m_odd)
    asserted = exl.resolve_exchange(MESH_16x16, B=4096, d=32, m=m_odd,
                                    fused_chunk=True)
    assert asserted is honest
    # an eligible pool keeps the flag: the discount applies identically
    # whether derived from m or caller-asserted
    derived = exl.resolve_exchange(MESH_16x16, B=4096, d=32, m=135_266_304)
    explicit = exl.resolve_exchange(MESH_16x16, B=4096, d=32, m=135_266_304,
                                    fused_chunk=True)
    assert explicit is derived


def test_resolve_clamps_caller_asserted_fused_flag():
    """An explicit ``fused=True`` cannot outrun the VMEM gate: when the pool
    is known and its per-device slab exceeds the fused engine's budget, the
    discount is clamped off — previously it leaked through and could
    mis-pick psum for an over-budget pool config."""
    m_big = 135_266_304                       # 34 MiB/device at 4 ranks: over
    assert not exl.fused_slab_eligible(m_big, 4)
    honest = exl.resolve_exchange(MESH_2x4, B=4096, d=32, m=m_big)
    asserted = exl.resolve_exchange(MESH_2x4, B=4096, d=32, m=m_big,
                                    fused=True)
    assert asserted is honest
    assert asserted is not exl.PSUM
    # the cost-table entry the clamp protects: with the discount leaked,
    # psum prices below the chunked strategies and would be mis-picked
    leaked = exl.lookup_cost(4, 4096, 32, fused=True)
    clamped = exl.lookup_cost(4, 4096, 32, fused=False)
    assert min(leaked, key=leaked.get) == "psum"
    assert min(clamped, key=clamped.get) != "psum"
    # a genuinely eligible slab keeps the explicit flag untouched
    assert exl.fused_slab_eligible(1 << 21, 4)


def test_tier_fetch_bytes_model():
    """Host-fetch cost term for the tiered store: each staged cold block
    crosses PCIe twice (fetch + writeback) per pool leaf."""
    assert exl.tier_fetch_bytes(0, 512) == 0
    assert exl.tier_fetch_bytes(3, 512) == 2 * 3 * 512 * 4
    assert exl.tier_fetch_bytes(3, 512, n_leaves=2) == 2 * exl.tier_fetch_bytes(3, 512)
    assert exl.tier_fetch_bytes(3, 512, itemsize=2) == exl.tier_fetch_bytes(3, 512) // 2


def test_eligibility_fallback():
    assert exl.RING.eligible(64, 4) and exl.ALL_TO_ALL.eligible(64, 4)
    assert not exl.RING.eligible(63, 4)
    assert not exl.ALL_TO_ALL.eligible(63, 4)
    assert not exl.RING.eligible(64, 1)
    assert exl.PSUM.eligible(63, 4)


def test_resolve_update_exchange():
    assert exl.resolve_update_exchange(None) is exl.PSUM
    assert exl.resolve_update_exchange(fake_mesh(data=8)) is exl.PSUM
    assert exl.resolve_update_exchange(MESH_2x4) is exl.ALL_TO_ALL
    old = exl.FORCED
    try:
        exl.FORCED = "psum"
        assert exl.resolve_update_exchange(MESH_2x4) is exl.PSUM
        exl.FORCED = "ring"    # ring has no update form -> psum
        assert exl.resolve_update_exchange(MESH_2x4) is exl.PSUM
    finally:
        exl.FORCED = old


def test_get_exchange_unknown():
    with pytest.raises(KeyError):
        exl.get_exchange("bcast")


# ----------------------------------------------------- sparse gate table

# dlrm-rm2 train_batch at 16x16: 65536 examples x 26 fields, d=64 would be
# the real cell; the table below uses the d=32 bench flavor the ROADMAP
# quotes.  What matters is the *shape* of the decisions, pinned here:

def test_sparse_worthwhile_single_host_always_sparse():
    assert exl.sparse_worthwhile(None, n_lookups=4096, d=32, m=1 << 21)


def test_sparse_worthwhile_2x4_bench_shape_sparse():
    assert exl.sparse_worthwhile(MESH_2x4, n_lookups=4096, d=32, m=1 << 21)


def test_sparse_worthwhile_pod_scale_element_vs_row():
    """The three-way split at pod scale: at 16x16 with a 65k global batch,
    FLAT element-level records (the ragged-budget fallback, m % d != 0)
    stay dense — the O(K log K) dedup sort on ~54M element locations erases
    the win; row-aligned records (hashed_row / freq) go sparse (index
    vector and sort d times smaller, all_to_all keeps owned slices local);
    and BUCKETED element records (the striped LMA layout, buckets == d) go
    sparse too — per-stripe sorts sharded over 'model' plus the in-kernel
    fold price the construction below the dense slab tax.  The last flip is
    what the bucketed layout was built for (ROADMAP item 1)."""
    n_lookups, d, m = 65536 * 26, 32, 135_266_304
    assert not exl.sparse_worthwhile(MESH_16x16, n_lookups, d, m,
                                     row_mode=False)
    assert exl.sparse_worthwhile(MESH_16x16, n_lookups, d, m,
                                 row_mode=False, buckets=d)
    assert exl.sparse_worthwhile(MESH_16x16, n_lookups, d, m, row_mode=True)
    # ... and both flips are the all_to_all exchange's doing: under the
    # replicated psum pair the same cells stay dense (the bucketed sort
    # cannot shard either — every rank needs the whole stream)
    old = exl.FORCED
    try:
        exl.FORCED = "psum"
        assert not exl.sparse_worthwhile(MESH_16x16, n_lookups, d, m,
                                         row_mode=True)
        assert not exl.sparse_worthwhile(MESH_16x16, n_lookups, d, m,
                                         row_mode=False, buckets=d)
    finally:
        exl.FORCED = old


def test_sparse_update_cost_fields():
    c = exl.sparse_update_cost(4, 4096, 32, 1 << 21)
    assert set(c) == {"dense", "sparse_psum", "sparse_all_to_all",
                      "dedup_sort"}
    assert c["sparse_all_to_all"] < c["sparse_psum"]
    assert c["dedup_sort"] > 0
    assert exl.dedup_sort_bytes(1) == 0.0


def test_dedup_sort_bytes_bucketed_paths():
    """The per-path dedup model: bucketed construction is strictly cheaper
    than flat at matched K (shallower per-stripe sorts x the measured
    batched-sort efficiency), the model-sharded variant divides by n_model
    exactly when the axis divides the bucket count, and degenerate bucket
    shapes (k % buckets != 0, one key per bucket) fall back to the flat
    charge — mirroring from_bucketed_locations' own fallback guards."""
    k, d = 1 << 17, 32
    flat = exl.dedup_sort_bytes(k)
    bucketed = exl.dedup_sort_bytes(k, buckets=d)
    assert 0 < bucketed < flat / exl.BUCKETED_SORT_SPEEDUP
    assert exl.dedup_sort_bytes(k, buckets=7) == flat       # ragged
    assert exl.dedup_sort_bytes(d, buckets=d) == exl.dedup_sort_bytes(d)
    c16 = exl.sparse_update_cost(16, k // d, d, 1 << 27, buckets=d)
    assert c16["dedup_sort"] == pytest.approx(bucketed / 16)
    # bucket count the axis does not divide -> replicated bucketed sort
    c_r = exl.sparse_update_cost(16, k // d, 24, 1 << 27, buckets=24)
    assert c_r["dedup_sort"] == pytest.approx(
        exl.dedup_sort_bytes((k // d) * 24, buckets=24))


# ----------------------------------------------- 2x4 parity (all schemes)

_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.signatures import synthetic_dense_store
from repro.dist import exchange as exl
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.embed import EmbeddingTable, get_scheme, list_schemes

assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)

for kind in list_schemes():
    scheme = get_scheme(kind)
    table = EmbeddingTable(scheme.build_config((512,), 16, 4096, seed=3))
    store = None
    if scheme.buffer_source == "signatures":
        store = synthetic_dense_store(512, 8, max_set=32, seed=2)
    elif scheme.buffer_source == "id_counts":
        store = rng.integers(0, 50, 512).astype(np.int64)
    bufs = table.make_buffers(store)
    params = table.init(jax.random.key(1))
    ids = jnp.asarray(rng.integers(0, 512, (64,), np.int32))
    want = table.embed(params, bufs, 0, ids)          # no mesh: oracle
    outs = {}
    for name in ("psum", "ring", "all_to_all"):
        exl.FORCED = name
        try:
            with use_mesh(mesh):
                outs[name] = table.embed(params, bufs, 0, ids)
        finally:
            exl.FORCED = None
        np.testing.assert_array_equal(np.asarray(outs[name]),
                                      np.asarray(want))
    print(kind, "forward parity OK (psum/ring/all_to_all bitwise)")

# the standalone set-reconstruction driver: row-sharded integer table +
# dp-sharded gids -> exact rows under every strategy
from repro.dist.sharded_memory import sharded_set_lookup
store = synthetic_dense_store(512, 8, max_set=32, seed=2)
gids = jnp.asarray(rng.integers(0, 512, (64,), np.int32))
want_sets = jnp.take(store.sets, gids, axis=0)
want_lens = jnp.take(store.lengths, gids, axis=0)
for name in ("psum", "ring", "all_to_all"):
    with use_mesh(mesh):
        got_sets = sharded_set_lookup(store.sets, gids, mesh, ("data",),
                                      exchange=name)
        got_lens = sharded_set_lookup(store.lengths, gids, mesh, ("data",),
                                      exchange=name)
    np.testing.assert_array_equal(np.asarray(got_sets),
                                  np.asarray(want_sets))
    np.testing.assert_array_equal(np.asarray(got_lens),
                                  np.asarray(want_lens))
    print("sharded_set_lookup", name, "OK")

print("ALL_EXCHANGE_FORWARD_OK")
"""


_TRAIN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.signatures import synthetic_dense_store
from repro.dist import exchange as exl
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.embed import EmbeddingTable, get_scheme
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sp

assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))

for kind in ("lma", "hashed_row", "freq"):
    scheme = get_scheme(kind)
    table = EmbeddingTable(scheme.build_config((512,), 16, 4096, seed=3))
    store = synthetic_dense_store(512, 8, max_set=32, seed=2) \
        if scheme.needs_signature_store else None
    bufs = table.make_buffers(store)
    params0 = {"embedding": table.init(jax.random.key(1))}

    def batch(step):
        r = np.random.default_rng(step)
        return (jnp.asarray(r.integers(0, 512, 64, np.int32)),
                jnp.asarray(r.normal(size=(64, 16)).astype(np.float32)))

    def loss_fn(p, ids, y):
        e = table.embed(p["embedding"], bufs, 0, ids)
        l = jnp.mean((e - y) ** 2)
        return l, {"l": l}

    def train(sparse, mesh_ctx, forced=None):
        params = jax.tree_util.tree_map(lambda x: x, params0)
        opt = opt_lib.adagrad(0.1, eps=1e-8)
        state = opt.init(params)
        vg = sp.sparse_value_and_grad(loss_fn) if sparse else \
            jax.value_and_grad(loss_fn, has_aux=True)
        def step(params, state, ids, y):
            (_, _m), g = vg(params, ids, y)
            u, state = opt.update(g, state, params)
            return opt_lib.apply_updates(params, u), state
        # one jit per train() call: the strategy is resolved at trace time,
        # and 10 re-traced eager steps x 4 runs x 3 schemes would flirt
        # with the subprocess timeout on a loaded machine
        jstep = jax.jit(step)
        exl.FORCED = forced
        try:
            for s in range(10):
                ids, y = batch(s)
                if mesh_ctx is None:
                    params, state = jstep(params, state, ids, y)
                else:
                    with use_mesh(mesh_ctx):
                        params, state = jstep(params, state, ids, y)
        finally:
            exl.FORCED = None
        return params

    a = np.asarray(train(False, None)["embedding"]["memory"])
    # psum / all_to_all pin the two sparse-update exchanges; ring pins the
    # ring lookup's BACKWARD path (its update exchange falls back to psum)
    for forced in ("psum", "ring", "all_to_all"):
        b = np.asarray(train(True, mesh, forced)["embedding"]["memory"])
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
        print(kind, forced, "10-step sparse training parity OK")

print("ALL_EXCHANGE_TRAIN_OK")
"""


_CSR_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.signatures import synthetic_dense_store
from repro.dist import exchange as exl
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.dist.sharded_memory import shard_csr, shard_csr_buffers
from repro.embed import EmbeddingTable, get_scheme

assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)

# a ragged CSR signature store built from the dense synthetic one
ds = synthetic_dense_store(512, 8, max_set=32, seed=2)
lengths = np.asarray(ds.lengths)
sets = np.asarray(ds.sets)
flat = np.concatenate([sets[i, : lengths[i]] for i in range(512)])
offsets = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
bufs = {"store_flat": jnp.asarray(flat),
        "store_offsets": jnp.asarray(offsets),
        "store_lengths": jnp.asarray(lengths)}

# shard_csr round-trip: per-rank re-based offsets reconstruct every row
flat_sh, offs_sh = shard_csr(flat, offsets, 4)
per = 512 // 4
for r in range(4):
    for v in range(per):
        s, e = offs_sh[r, v], offs_sh[r, v + 1]
        g = r * per + v
        np.testing.assert_array_equal(
            flat_sh[r, s:e], flat[offsets[g]: offsets[g + 1]])
print("shard_csr round-trip OK")

scheme = get_scheme("lma")
table = EmbeddingTable(scheme.build_config((512,), 16, 4096, seed=3))
params = table.init(jax.random.key(1))
ids = jnp.asarray(rng.integers(0, 512, (64,), np.int32))
want = table.embed(params, bufs, 0, ids)          # no mesh, raw CSR: oracle

sh_bufs = shard_csr_buffers(bufs, mesh)
assert "store_flat_sh" in sh_bufs and "store_flat" not in sh_bufs

for name in ("psum", "ring", "all_to_all"):
    exl.FORCED = name
    try:
        with use_mesh(mesh):
            got = table.embed(params, sh_bufs, 0, ids)
            raw = table.embed(params, bufs, 0, ids)   # unsharded CSR fallback
    finally:
        exl.FORCED = None
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(want))
    print("csr sharded lookup", name, "OK (and raw-CSR fallback)")

print("CSR_SHARDED_ALL_OK")
"""


# ----------------------------------- fused-chunked engine (ring/all_to_all)

_FUSED_CHUNK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.signatures import synthetic_dense_store
from repro.dist import exchange as exl
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.embed import EmbeddingTable, get_scheme, list_schemes
import repro.kernels.fused_embed.ops as fe

assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)

def build(kind):
    scheme = get_scheme(kind)
    table = EmbeddingTable(scheme.build_config((512,), 16, 4096, seed=3))
    store = None
    if scheme.buffer_source == "signatures":
        store = synthetic_dense_store(512, 8, max_set=32, seed=2)
    elif scheme.buffer_source == "id_counts":
        store = rng.integers(0, 50, 512).astype(np.int64)
    bufs = table.make_buffers(store)
    params = table.init(jax.random.key(1))
    ids = jnp.asarray(rng.integers(0, 512, (64,), np.int32))
    return table, bufs, params, ids

def run(fn, enabled, forced):
    fe.ENABLED = enabled
    exl.FORCED = forced
    try:
        if forced is None:
            return np.asarray(fn())
        with use_mesh(mesh):
            return np.asarray(fn())
    finally:
        exl.FORCED = None
        fe.ENABLED = True

# forward: fused-chunked vs the split-chunk oracle AND the replicated
# single-device lookup, bitwise, for every registered scheme
for kind in list_schemes():
    table, bufs, params, ids = build(kind)
    emb = lambda: table.embed(params, bufs, 0, ids)
    want = run(emb, True, None)                       # replicated oracle
    for name in ("ring", "all_to_all"):
        split = run(emb, False, name)
        fused = run(emb, True, name)
        np.testing.assert_array_equal(fused, split)
        np.testing.assert_array_equal(fused, want)
    print(kind, "fused-chunked forward bit-parity OK")

# gradients: the chunked engine's custom VJP (saved-location Pallas
# scatter) against the split path's XLA scatter-add and the replicated
# oracle — memory-pool cotangents to 1e-6
for kind in ("lma", "hashed_row"):
    table, bufs, params, ids = build(kind)
    y = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))

    def loss(p):
        e = table.embed(p, bufs, 0, ids)
        return jnp.mean((e - y) ** 2)

    g_fn = lambda: jax.grad(loss)(params)["memory"]
    g_ref = run(g_fn, True, None)
    for name in ("ring", "all_to_all"):
        g_split = run(g_fn, False, name)
        g_fused = run(g_fn, True, name)
        np.testing.assert_allclose(g_fused, g_split, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(g_fused, g_ref, atol=1e-6, rtol=1e-6)
    print(kind, "fused-chunked grad parity OK")

print("FUSED_CHUNK_ALL_OK")
"""


_VMEM_GATE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_FUSED_MAX_MEM_MB"] = "5"     # shrink the gate pre-import
import numpy as np
import jax, jax.numpy as jnp
from repro.core.allocation import alloc_hashed_elem
from repro.core.memory import init_memory, lookup
from repro.dist import exchange as exl
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.dist.sharded_memory import sharded_hashed_lookup
import repro.kernels.fused_embed.ops as fe

m, d, B = 1 << 22, 16, 256
m_local = m // 4                                # 4 MiB/device slab
assert not fe.fused_supported(m_local, 4)       # whole slab over the gate
assert fe.fused_chunk_supported(m_local, 4)     # but pow2 slab blocks fit
assert fe._chunk_blocks(m_local, 4) == 4        # 1 MiB tiles under 5-4 MiB
assert not exl.fused_slab_eligible(m, 4)
assert exl.fused_chunk_eligible(m, 4)

# pin that the over-gate slab actually takes the fused-chunked path: count
# the Pallas entry points the engine dispatches to
calls = {"fwd": 0, "gather": 0}
_fwd, _gather = fe.fused_chunk_fwd_pallas, fe.fused_chunk_gather_pallas
def spy_fwd(*a, **k):
    calls["fwd"] += 1
    return _fwd(*a, **k)
def spy_gather(*a, **k):
    calls["gather"] += 1
    return _gather(*a, **k)
fe.fused_chunk_fwd_pallas = spy_fwd
fe.fused_chunk_gather_pallas = spy_gather

mem = init_memory(jax.random.key(0), m, "normal", 0.1)
gids = jnp.asarray(np.random.default_rng(1).integers(0, 4096, (B,), np.int32))
mesh = make_mesh((2, 4), ("data", "model"))
oracle = np.asarray(lookup(mem, alloc_hashed_elem(gids, d, m, 7)))
for name in ("ring", "all_to_all"):
    exl.FORCED = name
    try:
        with use_mesh(mesh):
            got = sharded_hashed_lookup(mem, gids, d, m, 7, mesh, ("data",))
    finally:
        exl.FORCED = None
    np.testing.assert_array_equal(np.asarray(got), oracle)
assert calls["fwd"] > 0, calls      # in-kernel loc math + own-slab gather ran
assert calls["gather"] > 0, calls   # slab-TILED gather ran (whole-slab path
                                    # is gated off, so no other form could)
print("VMEM_GATE_CHUNKED_OK", calls)
"""


def _run_sub(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("REPRO_DIST_EXCHANGE", None)
    return subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=env, timeout=1800)


@pytest.mark.slow
def test_exchange_forward_parity_all_schemes_2x4():
    r = _run_sub(_PARITY_SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_EXCHANGE_FORWARD_OK" in r.stdout


@pytest.mark.slow
def test_exchange_sparse_training_parity_2x4():
    r = _run_sub(_TRAIN_SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_EXCHANGE_TRAIN_OK" in r.stdout


@pytest.mark.slow
def test_fused_chunked_parity_all_schemes_2x4():
    """The fused-chunked engine (one Pallas call per exchange chunk: in-VMEM
    location math + slab-masked gather) under ring and all_to_all is bitwise
    identical to the split-chunk oracle and the replicated single-device
    lookup for every registered scheme, forward and (to 1e-6) backward."""
    r = _run_sub(_FUSED_CHUNK_SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "FUSED_CHUNK_ALL_OK" in r.stdout


@pytest.mark.slow
def test_vmem_gate_over_slab_under_chunk_takes_fused_path_2x4():
    """With REPRO_FUSED_MAX_MEM_MB shrunk so the whole per-device slab
    exceeds the VMEM gate but power-of-two slab blocks fit, ring and
    all_to_all still take the fused-chunked path (pinned by counting Pallas
    entry-point dispatches) and stay bitwise identical to the replicated
    oracle — the tentpole case the chunk-level gate exists for."""
    r = _run_sub(_VMEM_GATE_SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "VMEM_GATE_CHUNKED_OK" in r.stdout


@pytest.mark.slow
def test_csr_sharded_store_parity_2x4():
    """The 'model'-sharded CSR signature store (shard_csr_buffers) through
    the public embed path: ragged sets reconstructed with
    Exchange.partial_sum_lookup are bit-identical to the replicated raw-CSR
    oracle under psum, ring and all_to_all — the store stops replicating
    without moving a single output bit."""
    r = _run_sub(_CSR_SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "CSR_SHARDED_ALL_OK" in r.stdout
