"""Kernel micro-bench: Pallas (interpret on CPU) vs pure-jnp reference.

A CPU bench: the Pallas interpreter and XLA:CPU are NOT a performance
target — the numbers recorded here document (a) correctness at benchmark
shapes and (b) the jnp-reference wall time that the roofline's memory-term
is sanity-checked against.  None of them is a device time.  Run it with
``JAX_PLATFORMS=cpu``: its sharded-lookup child refuses a TPU host.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.allocation import LMAParams
from repro.kernels.cin.ref import cin_ref
from repro.kernels.dot_interaction.ref import dot_interaction_ref
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.lma_locations.ops import reference as lma_ref

from benchmarks.common import ART_DIR, save_csv, time_fn


# Sharded-lookup micro-bench: run in a subprocess with 8 forced host devices
# (this process must keep its single real device).  Times the sharded LMA
# lookup on a (2, 4) ('data','model') mesh against the replicated-memory
# baseline — once per exchange strategy (psum fused/split, ring, all_to_all;
# repro/dist/exchange.py), with the chunked strategies timed in BOTH engine
# forms (fused-chunked Pallas engine vs split), interleaved rep-for-rep so
# the fused-vs-split comparison is drift-free — and reports the
# paper-critical traffic numbers: per-device gathered bytes are O(B*d) and
# per-device resident memory m/n_model, independent of the total budget.
# check_regression.py gates the best-strategy sharded/replicated gap and the
# fused-chunked win (sharded_gap_failures).
_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import json, time
import numpy as np
import jax, jax.numpy as jnp
from repro.core.allocation import LMAParams, alloc_lma
from repro.core.memory import init_memory, lookup
from repro.core.signatures import synthetic_dense_store
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.dist.sharded_memory import sharded_lma_lookup

mesh = make_mesh((2, 4), ("data", "model"))
B, D, M, N = 4096, 32, 1 << 21, 8192
lma = LMAParams(d=D, m=M, n_h=4, max_set=32, seed=7)
store = synthetic_dense_store(N, 64, max_set=32, seed=1)
mem = init_memory(jax.random.key(0), M, "normal", 0.1)
gids = jnp.asarray(np.random.default_rng(0).integers(0, N, (B,), np.int32))

# pin the engine state per measurement so an inherited REPRO_FUSED_EMBED=0
# cannot make both rows time the split path
import repro.kernels.fused_embed.ops as feops

base = jax.jit(lambda m_, g: lookup(m_, alloc_lma(lma, store, g)))

def jit_exchange(name, enabled):
    feops.ENABLED = enabled
    with use_mesh(mesh):
        sh = jax.jit(lambda m_, s, l, g: sharded_lma_lookup(
            m_, s, l, g, lma, mesh, ("data",), exchange=name))
        jax.block_until_ready(sh(mem, store.sets, store.lengths, gids))
    return sh

# Every variant — replicated baseline included — is timed in ONE
# round-robin: one rep of each per round, min across rounds.  Every number
# this script reports feeds a RATIO gate (fused vs split, best strategy vs
# replicated; check_regression.sharded_gap_failures), so the two sides of
# each ratio must sample identical machine state — timing the baseline
# minutes before the strategies lets thermal/scheduler drift manufacture or
# hide a regression, and min (not median) strips the jitter that survives
# interleaving.
args4 = lambda: (mem, store.sets, store.lengths, gids)
variants = {
    "replicated": (base, (mem, gids)),
    "psum_fused": (jit_exchange("psum", True), args4()),
    "psum_split": (jit_exchange("psum", False), args4()),
    "ring_split": (jit_exchange("ring", False), args4()),
    "ring_fused": (jit_exchange("ring", True), args4()),
    "a2a_split": (jit_exchange("all_to_all", False), args4()),
    "a2a_fused": (jit_exchange("all_to_all", True), args4()),
}
feops.ENABLED = True
samples = {name: [] for name in variants}
for rnd in range(64):
    for name, (f, a) in variants.items():
        t0 = time.perf_counter()
        jax.block_until_ready(f(*a))
        if rnd >= 4:  # first rounds re-warm every executable
            samples[name].append(time.perf_counter() - t0)
us = {name: float(np.min(s) * 1e6) for name, s in samples.items()}
t_base, t_fused, t_split = us["replicated"], us["psum_fused"], us["psum_split"]
t_ring, t_ring_fused = us["ring_split"], us["ring_fused"]
t_a2a, t_a2a_fused = us["a2a_split"], us["a2a_fused"]

n_dp, n_model = 2, 4
strategies = {"psum": min(t_fused, t_split),
              "ring": min(t_ring, t_ring_fused),
              "all_to_all": min(t_a2a, t_a2a_fused)}
best = min(strategies, key=strategies.get)
print(json.dumps({
    "mesh": "2x4", "B": B, "d": D, "m": M,
    "replicated_us": round(t_base, 1),
    "sharded_fused_us": round(t_fused, 1),
    "sharded_split_us": round(t_split, 1),
    "sharded_ring_us": round(t_ring, 1),
    "sharded_ring_fused_us": round(t_ring_fused, 1),
    "sharded_all_to_all_us": round(t_a2a, 1),
    "sharded_all_to_all_fused_us": round(t_a2a_fused, 1),
    "best_strategy": best,
    "best_strategy_us": round(strategies[best], 1),
    "sharded_over_replicated": round(strategies[best] / t_base, 3),
    "replicated_gathered_bytes_per_device": B * D * 4,
    "sharded_gathered_bytes_per_device": (B // n_dp) * D * 4,
    "replicated_resident_memory_bytes": M * 4,
    "sharded_resident_memory_bytes": M // n_model * 4,
}))
"""


def bench_sharded_lookup() -> dict:
    """A CPU bench: the child forces 8 virtual host devices.  It refuses to
    start on a TPU host — this process already holds the chip, so a child
    that reached for it would fail or hang on the device lock."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "bench_sharded_lookup is a CPU bench (8 virtual host devices in "
            "a child process) and this process holds the TPU; run the "
            "kernel bench with JAX_PLATFORMS=cpu")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    try:
        r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                           capture_output=True, text=True, env=env,
                           timeout=900)
    except subprocess.TimeoutExpired:
        return {"error": "sharded-lookup subprocess timed out (900s)"}
    if r.returncode != 0:
        return {"error": r.stderr[-2000:]}
    return json.loads(r.stdout.strip().splitlines()[-1])


def modeled_lookup_bytes(n: int, s: int, d: int) -> dict:
    """Modeled HBM bytes moved per batch lookup (n values, set width s,
    d locations each; 4-byte elements).

    split: read sets + WRITE the [N, d] int32 location tensor + READ it back
    + the gathered memory reads + write the [N, d] output.
    fused: locations never leave VMEM — the 2 * N*d*4 location-tensor
    round-trip disappears; sets stream in, gathers + output remain."""
    loc_tensor = n * d * 4
    gather_io = n * s * 4 + n * d * 4 + n * d * 4   # sets + gather + out
    return {
        "split": gather_io + 2 * loc_tensor,
        "fused": gather_io,
        "location_tensor_bytes": loc_tensor,
        "saved": 2 * loc_tensor,
    }


def _time_threaded(step, carry, *static, warmup: int = 2, iters: int = 10):
    """Median us/call of a donated step fn, threading (params, state)
    outputs back in so buffer donation stays legal across timed calls."""
    import time

    import jax
    for _ in range(warmup):
        carry = step(*carry, *static)
        jax.block_until_ready(carry)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        carry = step(*carry, *static)
        jax.block_until_ready(carry)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def modeled_update_bytes(m: int, k_idx: int, d: int) -> dict:
    """Modeled HBM bytes for one memory-pool Adagrad step (4-byte elems).

    dense: the VJP materializes a zeros[m] gradient and scatter-adds the
    batch contributions (1 [m] write + K*d element writes), then the
    optimizer streams read g / read acc / write acc / write upd and apply
    streams read p / read upd / write p — 8 full [m] passes in all.
    sparse: indices + values stream in, acc rows gather + scatter, p rows
    gather + scatter — O(K*d), no [m] pass at all.  This is the quantity
    the sparse engine optimizes (same accounting style as
    ``modeled_lookup_bytes``); ``check_regression.py`` gates its >= 3x
    speedup, because interpret/CPU wall-clock is scatter-serialization
    bound (XLA:CPU scatters ~250 ns/row) and understates the win the way
    the fused-lookup CPU numbers understate VMEM reuse."""
    kd = k_idx * d
    dense = 8 * m * 4 + kd * 4
    sparse = k_idx * 4 + 2 * kd * 4 + 4 * kd * 4
    return {"dense": dense, "sparse": sparse,
            "speedup": round(dense / max(sparse, 1), 2)}


def bench_sparse_update(rows: list, out: list) -> dict:
    """sparse vs dense memory-pool optimizer step at the paper shape
    (m=2^21, B=4096 lookups, d=32), plus an end-to-end lma train step.
    check_regression.py requires the modeled >= 3x advantage AND that the
    measured sparse update stays strictly faster than dense.

    The sparse gradient is built exactly as a training step builds it: a
    4096-lookup batch drawn from the repo's CTR traffic model (head-heavy,
    like real recsys ids), row-allocated by the ``freq`` scheme (the
    row-aligned pool layout production row-wise sparse optimizers assume)
    and deduped — the unique touched rows are what the sparse update
    scales with, which is the entire point.  The dense twin runs the
    classic O(m) Adagrad pass over the same (densified) gradient."""
    from repro.core.memory import init_memory
    from repro.data.synthetic_ctr import CTRGenerator, CTRSpec
    from repro.embed import get_scheme
    from repro.optim import optimizers as opt_lib
    from repro.optim import sparse as sp
    from repro.train.trainer import throughput_stats

    m, B, d = 1 << 21, 4096, 32
    shape = f"{B}x{d}@m=2^21"
    rng = np.random.default_rng(7)
    # repo-default CTR field scale (CTRSpec draws vocabs in [200, 2000]):
    # a hot field's 4096-lookup batch touches ~800 unique rows of the pool
    spec = CTRSpec(n_fields=1, n_dense=0, vocab_sizes=(2048,), seed=3)
    ids = jnp.asarray(CTRGenerator(spec).batch(B, 0)["sparse"][:, 0])
    scheme = get_scheme("freq")
    fcfg = scheme.build_config((65536,), d, m, seed=5)
    frows = scheme.sparse_row_ids(fcfg, {}, ids)
    vals = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))
    sg = jax.jit(lambda r, v: sp.from_locations(r, v, (m // d, d)))(
        frows, vals)
    n_rows = int(np.asarray(jnp.sum(sg.indices < m // d)))
    g_dense = sg.densify().reshape(-1)
    mem = init_memory(jax.random.key(0), m, "normal", 0.1)

    def one_step(opt):
        def step(p, s, g):
            u, s = opt.update(g, s, p)
            return opt_lib.apply_updates(p, u), s
        return jax.jit(step, donate_argnums=(0, 1))

    for name, opt, g in (
            ("sparse_update_adagrad", sp.sparse_adagrad(0.05), sg),
            ("dense_update_adagrad", opt_lib.adagrad(0.05), g_dense)):
        params = {"memory": mem.copy()}     # each run donates its own pool
        us = _time_threaded(one_step(opt), (params, opt.init(params)),
                            {"memory": g})
        rows.append((name, shape, round(us, 1)))
    s_us = dict((r[0], r[2]) for r in rows)
    upd_bytes = modeled_update_bytes(m, B, d)
    out.append(
        f"kernels sparse_update_adagrad {shape}: "
        f"{s_us['sparse_update_adagrad']:.0f} us vs dense "
        f"{s_us['dense_update_adagrad']:.0f} us "
        f"({s_us['dense_update_adagrad'] / max(s_us['sparse_update_adagrad'], 1e-9):.2f}x wall; "
        f"modeled HBM {upd_bytes['sparse']/2**20:.1f} MiB vs "
        f"{upd_bytes['dense']/2**20:.1f} MiB/step = "
        f"{upd_bytes['speedup']:.0f}x; "
        f"{n_rows} unique rows touched of {m // d})")

    # end-to-end lma train step (sparse grads + sparse adagrad), same shape
    from repro.core.signatures import synthetic_dense_store
    from repro.embed import EmbeddingTable
    scheme = get_scheme("lma")
    table = EmbeddingTable(scheme.build_config((65536,), d, m, seed=5))
    store = synthetic_dense_store(65536, 64, max_set=32, seed=2)
    bufs = table.make_buffers(store)
    params = {"embedding": table.init(jax.random.key(1))}
    ids = jnp.asarray(rng.integers(0, 65536, (B,), np.int32))
    y = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))

    def loss_fn(p):
        e = table.embed(p["embedding"], bufs, 0, ids)
        l = jnp.mean((e - y) ** 2)
        return l, {"l": l}

    opt = sp.sparse_adagrad(0.05)

    def step(p, s):
        (_, _m), g = sp.sparse_value_and_grad(loss_fn)(p)
        u, s = opt.update(g, s, p)
        return opt_lib.apply_updates(p, u), s

    us = _time_threaded(jax.jit(step, donate_argnums=(0, 1)),
                        (params, opt.init(params)))
    rows.append(("train_step_lma", shape, round(us, 1)))
    tp = throughput_stats([us / 1e6], lookups_per_step=B)
    out.append(f"kernels train_step_lma {shape}: {us:.0f} us/step "
               f"({tp['steps_per_sec']:.1f} steps/s, "
               f"{tp['lookups_per_sec']:,.0f} lookups/s)")
    return upd_bytes


def bench_guarded_step(rows: list, out: list) -> dict:
    """Cost of the resilience layer's non-finite step guard at the paper
    shape: the full lma train step (sparse grads + sparse adagrad, the
    ``train_step_lma`` setup) built twice through the shared step factory
    (``repro.resilience.guard.make_step``) — once unguarded (the pre-guard
    fast path: no checks, no cond) and once guarded (in-jit isfinite +
    magnitude scan over loss and every gradient leaf, update under
    ``lax.cond``).  ``check_regression.py::guard_overhead_failures`` gates
    the ratio at <= GUARD_OVERHEAD_MAX (1.05): always-on protection must
    stay within 5% of the unguarded step."""
    from repro.core.signatures import synthetic_dense_store
    from repro.embed import EmbeddingTable, get_scheme
    from repro.optim import sparse as sp
    from repro.resilience import guard as guard_lib

    m, B, d = 1 << 21, 4096, 32
    shape = f"{B}x{d}@m=2^21"
    rng = np.random.default_rng(7)
    scheme = get_scheme("lma")
    table = EmbeddingTable(scheme.build_config((65536,), d, m, seed=5))
    store = synthetic_dense_store(65536, 64, max_set=32, seed=2)
    bufs = table.make_buffers(store)
    ids = jnp.asarray(rng.integers(0, 65536, (B,), np.int32))
    y = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))

    def loss_fn(p, batch):
        e = table.embed(p["embedding"], bufs, 0, ids)
        l = jnp.mean((e - y) ** 2)
        return l, {"l": l}

    opt = sp.sparse_adagrad(0.05)
    variants = {}
    for name, guarded in (("train_step_unguarded", False),
                          ("train_step_guarded", True)):
        step = guard_lib.make_step(loss_fn, opt, sparse_grads=True,
                                   guard=guarded, donate=True)

        def carry_step(p, s, batch, fault, _step=step):
            p, s, *_ = _step(p, s, batch, fault)
            return p, s

        params = {"embedding": table.init(jax.random.key(1))}
        variants[name] = [carry_step, (params, opt.init(params))]

    # Interleave the timed iterations: the two variants are within a few
    # percent of each other, so timing them in separate blocks lets slow
    # machine-state drift (thermal throttling, background load) bias the
    # ratio by more than the effect being measured.  Alternating per
    # iteration makes drift hit both variants equally.
    import time
    warmup, iters = 2, 16
    samples = {name: [] for name in variants}
    for it in range(warmup + iters):
        for name, v in variants.items():
            t0 = time.perf_counter()
            v[1] = v[0](*v[1], {}, np.float32(1.0))
            jax.block_until_ready(v[1])
            if it >= warmup:
                samples[name].append(time.perf_counter() - t0)
    us = {name: float(np.median(s) * 1e6) for name, s in samples.items()}
    for name in ("train_step_unguarded", "train_step_guarded"):
        rows.append((name, shape, round(us[name], 1)))
    overhead = us["train_step_guarded"] / max(us["train_step_unguarded"], 1e-9)
    doc = {"guarded_us": round(us["train_step_guarded"], 1),
           "unguarded_us": round(us["train_step_unguarded"], 1),
           "overhead": round(overhead, 4)}
    out.append(
        f"kernels guarded_step {shape}: guarded "
        f"{us['train_step_guarded']:.0f} us vs unguarded "
        f"{us['train_step_unguarded']:.0f} us "
        f"({(overhead - 1) * 100:+.1f}% overhead; gate <= +5%)")
    return doc


def bench_tiered(rows: list, out: list) -> dict:
    """Cost of the tiered store (``repro.tier``) at the paper shape: an
    m=2^21 pool under a quarter-pool HBM budget (512-slot blocks), head-heavy
    CTR traffic routed by the ``freq`` scheme.

    ``tiered_lookup_hot`` / ``tiered_lookup_cold``
        the compact-pool gather (``remap_locations`` binary search +
        ``jnp.take``) with every touched block resident in the hot slab vs
        landing in the stage region — the device-side tax of tiering, paid
        on every lookup.  Both are asserted bit-identical to the full-pool
        gather before timing.
    ``host_fetch_bandwidth``
        one staged-buffer ``jax.device_put`` (the async prefetch's copy) —
        the host->HBM bandwidth the cold tier's real price is set by.
    ``train_step_tiered`` / ``train_step_resident``
        the end-to-end comparison behind
        ``check_regression.tiered_slowdown_failures``: a full adagrad train
        step driven through the TierController (writeback + EMA observe +
        stage + install + compact-pool step) vs the same model on the
        fully-resident pool.  Interleaved timing, like the guard bench.
    """
    from repro.data.synthetic_ctr import CTRGenerator, CTRSpec
    from repro.embed import EmbeddingTable, get_scheme
    from repro.optim import optimizers as opt_lib
    from repro.tier import TierController, TieredStore, remap_locations, \
        split_batch

    m, B, d, block = 1 << 21, 4096, 32, 512
    n_blocks = m // block
    hot_budget_slots = m // 4
    shape = f"{B}x{d}@m=2^21"
    rng = np.random.default_rng(13)
    scheme = get_scheme("freq")
    fcfg = scheme.build_config((65536,), d, m, seed=5)
    table = EmbeddingTable(fcfg)

    # head-heavy CTR traffic over a 2048-id field: the ~1k hot ids own
    # dedicated head rows, the tail row-hashes into a recurring working set
    # — the skew the observed-count re-tiering is built to exploit
    spec = CTRSpec(n_fields=1, n_dense=0, vocab_sizes=(2048,), seed=3)
    gen = CTRGenerator(spec)
    sample = np.concatenate([gen.batch(B, s)["sparse"][:, 0]
                             for s in range(4)])
    bufs = table.make_buffers(
        np.bincount(sample, minlength=fcfg.total_vocab).astype(np.int64))
    locate = jax.jit(lambda g: scheme.locations(fcfg, bufs, g))
    loc_s = np.asarray(locate(jnp.asarray(sample, jnp.int32)))
    blocks_s, counts_s = np.unique(loc_s // block, return_counts=True)
    bcounts = np.zeros(n_blocks, np.float64)
    bcounts[blocks_s] = counts_s

    # stage capacity: worst observed cold-touch count under the seeded hot
    # set, with 2x headroom for post-retier drift (overflow raises — the
    # store's honest failure mode — so a blown margin fails loudly)
    order = np.lexsort((np.arange(n_blocks), -bcounts))
    hot_preview = np.sort(order[: hot_budget_slots // block])
    worst = 1
    for s in range(8):
        loc = np.asarray(locate(jnp.asarray(
            gen.batch(B, 100 + s)["sparse"][:, 0], jnp.int32)))
        worst = max(worst, np.setdiff1d(np.unique(loc // block),
                                        hot_preview).size)
    cap = 2 * worst + 8

    emb0 = table.init(jax.random.key(1))
    full = emb0["memory"]
    st = TieredStore(np.asarray(full), hot_budget_slots, block=block,
                     stage_blocks=cap, counts=bcounts)
    gather = jax.jit(lambda c, l, h, s_, b: jnp.take(
        c, remap_locations(l, h, s_, b)))

    # hot: every location in a resident block (remap overhead only)
    off = rng.integers(0, block, (B, d))
    loc_hot = jnp.asarray(
        st.hot_ids[rng.integers(0, st.hot_ids.size, (B, d))] * block + off,
        jnp.int32)
    compact = st.initial_compact()
    tb = st.batch_tier_buffers()
    args_hot = (compact, loc_hot, tb["tier_hot_ids"], tb["tier_stage_ids"],
                tb["tier_block"])
    np.testing.assert_array_equal(np.asarray(gather(*args_hot)),
                                  np.asarray(jnp.take(full, loc_hot)))
    us_hot = time_fn(gather, *args_hot)

    # cold: every location in a staged block (same device math — the remap
    # is membership-oblivious; the cold tier's real cost is the host fetch)
    cold_all = np.setdiff1d(np.arange(n_blocks), st.hot_ids)
    sel = np.sort(rng.choice(cold_all, size=min(cap, cold_all.size),
                             replace=False))
    loc_cold = jnp.asarray(
        sel[rng.integers(0, sel.size, (B, d))] * block + off, jnp.int32)
    st.stage(sel)
    compact = st.install({"memory": compact})["memory"]
    tb = st.batch_tier_buffers()
    args_cold = (compact, loc_cold, tb["tier_hot_ids"], tb["tier_stage_ids"],
                 tb["tier_block"])
    np.testing.assert_array_equal(np.asarray(gather(*args_cold)),
                                  np.asarray(jnp.take(full, loc_cold)))
    us_cold = time_fn(gather, *args_cold)
    us_plain = time_fn(jax.jit(lambda m_, l: jnp.take(m_, l)), full, loc_hot)
    rows.append(("tiered_lookup_hot", shape, round(us_hot, 1)))
    rows.append(("tiered_lookup_cold", shape, round(us_cold, 1)))
    out.append(
        f"kernels tiered_lookup {shape}: hot {us_hot:.0f} us / cold "
        f"{us_cold:.0f} us vs full-pool take {us_plain:.0f} us "
        f"(remap adds {us_hot - us_plain:+.0f} us; both bit-exact)")

    # host->device staging bandwidth: the async prefetch's device_put
    sbuf = np.zeros((1024, block), np.float32)
    us_fetch = time_fn(jax.device_put, sbuf)
    gbps = sbuf.nbytes / (us_fetch / 1e6) / 1e9
    rows.append(("host_fetch_bandwidth", f"1024x{block}@f32",
                 round(us_fetch, 1)))
    out.append(f"kernels host_fetch_bandwidth: {sbuf.nbytes / 2**20:.0f} MiB "
               f"staged in {us_fetch:.0f} us ({gbps:.1f} GB/s host->device)")

    # end-to-end: controller-driven tiered train step vs resident twin
    st2 = TieredStore(np.asarray(full), hot_budget_slots, block=block,
                      stage_blocks=cap, counts=bcounts)
    y = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))

    def raw_batch_fn(i):
        return {"ids": jnp.asarray(gen.batch(B, i)["sparse"][:, 0],
                                   jnp.int32), "y": y}

    ctrl = TierController(st2, raw_batch_fn, lambda b: locate(b["ids"]),
                          retier_every=8)
    opt = opt_lib.adagrad(0.05)

    def make_step(loss):
        def step(p, s_, batch):
            g = jax.grad(loss)(p, batch)
            u, s_ = opt.update(g, s_, p)
            return opt_lib.apply_updates(p, u), s_
        return jax.jit(step, donate_argnums=(0, 1))

    def loss_tiered(p, batch):
        clean, tier = split_batch(batch)
        e = table.embed(p["embedding"], {**bufs, **tier}, 0, clean["ids"])
        return jnp.mean((e - clean["y"]) ** 2)

    def loss_res(p, batch):
        e = table.embed(p["embedding"], bufs, 0, batch["ids"])
        return jnp.mean((e - batch["y"]) ** 2)

    step_t, step_r = make_step(loss_tiered), make_step(loss_res)
    params_t = {"embedding": {"memory": st2.initial_compact()}}
    params_r = {"embedding": {"memory": jnp.asarray(np.asarray(full))}}
    opt_t, opt_r = opt.init(params_t), opt.init(params_r)

    import time
    warm, iters = 4, 12
    samples = {"train_step_tiered": [], "train_step_resident": []}
    for i in range(warm + iters):
        t0 = time.perf_counter()
        params_t, opt_t, _ = ctrl.pre_step(i, params_t, opt_t)
        params_t, opt_t = step_t(params_t, opt_t, ctrl.batch_fn(i))
        jax.block_until_ready(params_t)
        if i >= warm:
            samples["train_step_tiered"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        params_r, opt_r = step_r(params_r, opt_r, raw_batch_fn(i))
        jax.block_until_ready(params_r)
        if i >= warm:
            samples["train_step_resident"].append(time.perf_counter() - t0)
    us = {n: float(np.median(s) * 1e6) for n, s in samples.items()}
    for name in ("train_step_tiered", "train_step_resident"):
        rows.append((name, shape, round(us[name], 1)))
    slowdown = us["train_step_tiered"] / max(us["train_step_resident"], 1e-9)
    s2 = st2.stats
    staged = s2["staged_blocks"] / max(s2["stage_steps"], 1)
    doc = {"tiered_us": round(us["train_step_tiered"], 1),
           "resident_us": round(us["train_step_resident"], 1),
           "slowdown": round(slowdown, 4),
           # the slowdown gate's 2x bound assumes the async stage overlaps
           # the step — impossible on a single-core host, where
           # check_regression applies the serialized bound instead
           "host_cpus": os.cpu_count(),
           "hot_rows": st2.hot_slots, "cold_rows": m - st2.hot_slots,
           "stage_capacity_blocks": int(cap),
           "staged_blocks_per_step": round(staged, 1),
           "host_fetch_bytes_per_step": int(
               s2["host_fetch_bytes"] / max(s2["stage_steps"], 1)),
           "host_fetch_gbps": round(gbps, 2),
           "lookup_hot_us": round(us_hot, 1),
           "lookup_cold_us": round(us_cold, 1)}
    out.append(
        f"kernels tiered train step {shape}: tiered "
        f"{us['train_step_tiered']:.0f} us vs resident "
        f"{us['train_step_resident']:.0f} us ({slowdown:.2f}x; hot "
        f"{st2.hot_slots / 2**18:.1f} MiB of {m / 2**18:.0f} MiB pool, "
        f"{staged:.0f} blocks staged/step, "
        f"{doc['host_fetch_bytes_per_step'] / 2**10:.0f} KiB host fetch/step)")
    return doc


def bench_ckpt(rows: list, out: list) -> dict:
    """Durability tax of the checkpoint layer (``repro.checkpoint``) at the
    paper pool shape: an m=2^21 f32 memory-pool leaf plus its Adagrad moment
    (16 MiB of integrity-chunked pool state) and a small dense head.

    ``ckpt_full``
        a blocking full/base save — every leaf serialized, whole-tree
        sha256 + per-chunk bit-sums computed, tmp + ``os.replace`` commit.
    ``ckpt_delta``
        an incremental save after head-heavy CTR traffic touched the pool:
        only the integrity chunks dirtied since the base are persisted
        (cumulative-since-base, so any step replays as one base + one
        delta regardless of chain position).
    ``ckpt_restore_chain``
        restore of a delta step — replays (base, delta) with full
        verification — against the doc's ``restore_full_us`` single-file
        path.

    check_regression gates the fresh ledger absolutely
    (``ckpt_delta_failures``): delta payload <= 25% of the full payload
    and the chain restore <= 2x the full restore.
    """
    import shutil
    import tempfile
    import time

    from repro.checkpoint.manager import CheckpointManager
    from repro.resilience import integrity as integ_lib

    m = 1 << 21
    chunk = integ_lib.CHUNK
    n_chunks = m // chunk                      # 256 integrity chunks
    shape = "m=2^21x2pool"
    rng = np.random.default_rng(0)
    state = {
        "params": {"memory": rng.normal(0, 0.1, m).astype(np.float32),
                   "w": rng.normal(0, 1, (256, 64)).astype(np.float32)},
        "opt": {"memory": np.zeros(m, np.float32)},
        "step": np.asarray(0, np.int32),
    }

    def touch(seed):
        # head-heavy CTR traffic: the hot head of the pool takes the step's
        # updates, so a delta carries ~32 of the 256 chunks
        r = np.random.default_rng(seed)
        slots = r.integers(0, 32 * chunk, (4096,))
        state["params"]["memory"][slots] += 1e-3
        state["opt"]["memory"][slots] += 1e-3
        return slots

    def med_us(samples):
        return float(np.median(samples) * 1e6)

    tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        # full saves: a non-delta manager, one fresh step per sample
        mgr_full = CheckpointManager(os.path.join(tmp, "full"), keep=2)
        full_t = []
        for s in (1, 2, 3):
            state["step"] = np.asarray(s, np.int32)
            t0 = time.perf_counter()
            mgr_full.save(s, state)
            full_t.append(time.perf_counter() - t0)
        full_bytes = mgr_full.last_save_bytes
        restore_full_t = []
        for _ in range(3):
            t0 = time.perf_counter()
            mgr_full.restore()
            restore_full_t.append(time.perf_counter() - t0)

        # delta chain: base at 0, then incremental saves under CTR traffic
        mgr = CheckpointManager(os.path.join(tmp, "delta"), keep=8,
                                delta=True, compact_every=16)
        state["step"] = np.asarray(0, np.int32)
        mgr.save(0, state)
        delta_t = []
        last = 0
        for s in (10, 20, 30):
            mgr.mark_dirty_slots(touch(s))
            state["step"] = np.asarray(s, np.int32)
            t0 = time.perf_counter()
            mgr.save(s, state)
            delta_t.append(time.perf_counter() - t0)
            last = s
        delta_bytes = mgr.last_save_bytes
        with open(os.path.join(tmp, "delta", f"step_{last:010d}",
                               "manifest.json")) as f:
            man = json.load(f)
        dirty = {int(i) for info in man["delta"].values()
                 for i in info["chunks"]}
        restore_chain_t = []
        for _ in range(3):
            t0 = time.perf_counter()
            got, _tree = mgr.restore()
            restore_chain_t.append(time.perf_counter() - t0)
        assert got == last and mgr.last_restore_report["chain_len"] == 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    us_full, us_delta = med_us(full_t), med_us(delta_t)
    us_rfull, us_rchain = med_us(restore_full_t), med_us(restore_chain_t)
    ratio = delta_bytes / max(full_bytes, 1)
    rows.append(("ckpt_full", shape, round(us_full, 1)))
    rows.append(("ckpt_delta", shape, round(us_delta, 1)))
    rows.append(("ckpt_restore_chain", shape, round(us_rchain, 1)))
    doc = {"full_save_us": round(us_full, 1),
           "delta_save_us": round(us_delta, 1),
           "restore_full_us": round(us_rfull, 1),
           "restore_chain_us": round(us_rchain, 1),
           "full_bytes": int(full_bytes),
           "delta_bytes": int(delta_bytes),
           "delta_ratio": round(ratio, 4),
           "chain_len": 1,
           "dirty_chunks": len(dirty),
           "total_chunks": n_chunks,
           "touch_rate": round(len(dirty) / n_chunks, 4)}
    out.append(
        f"kernels ckpt {shape}: delta save {us_delta:.0f} us / "
        f"{delta_bytes / 2**20:.1f} MiB vs full {us_full:.0f} us / "
        f"{full_bytes / 2**20:.1f} MiB ({ratio:.1%} of full payload, "
        f"{len(dirty)}/{n_chunks} chunks dirty); restore chain "
        f"{us_rchain:.0f} us vs full {us_rfull:.0f} us "
        f"({us_rchain / max(us_rfull, 1e-9):.2f}x)")
    return doc


def bench_dedup_sort(rows: list, out: list) -> None:
    """The SparseGrad construction tax, swept over K = B*d in 2^13..2^17,
    three ways on the SAME striped locations:

    ``sparse_dedup_sort``
        flat path — ``sparse.from_locations``: one O(K log K) argsort +
        segment-sum dedup.  At near-uniform traffic on CPU this term alone
        can erase the sparse-vs-dense win — the reason pod-scale lma cells
        used to stay dense.
    ``sparse_dedup_bucketed``
        ``sparse.from_bucketed_locations``: d per-stripe packed-key sorts
        (log(K/d) deep, batched), dedup deferred to the update kernel.
    ``sparse_dedup_inkernel``
        the full replacement pipeline — bucketed construction + the
        adagrad update consuming the duplicate stream directly
        (``unique=False``, in-kernel fold); its flat twin is
        sparse_dedup_sort + the sparse_update_adagrad row.

    ``check_regression.dedup_speedup_failures`` gates flat/bucketed >= 3x
    at K=2^17, the measurement behind ``exchange.BUCKETED_SORT_SPEEDUP``.
    """
    from repro.dist import exchange as exl
    from repro.kernels.sparse_update import ops as su
    from repro.optim import sparse as sp

    m, d = 1 << 21, 32
    stripe = m // d
    rng = np.random.default_rng(11)
    for B in (256, 512, 1024, 2048, 4096):
        k = B * d
        shape = f"{B}x{d}@m=2^21"
        # near-uniform traffic within each stripe: the worst case for the
        # dedup (few duplicates), laid out bucketed-by-construction the way
        # the striped allocator emits it
        loc = jnp.asarray(np.arange(d)[None, :] * stripe
                          + rng.integers(0, stripe, (B, d)), jnp.int32)
        vals = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))
        flat = jax.jit(lambda l, v: sp.from_locations(l, v, (m,)).indices)
        buck = jax.jit(
            lambda l, v: sp.from_bucketed_locations(l, v, (m,)).indices)
        acc = jnp.full((m,), 0.1, jnp.float32)

        def inkernel(l, v, a):
            g = sp.from_bucketed_locations(l, v, (m,))
            u, st = su.sparse_update("adagrad", g.indices, g.values, (a,),
                                     unique=False, lr=0.05)
            return u, st
        us_f = time_fn(flat, loc, vals)
        us_b = time_fn(buck, loc, vals)
        us_k = time_fn(jax.jit(inkernel), loc, vals, acc)
        rows.append(("sparse_dedup_sort", shape, round(us_f, 1)))
        rows.append(("sparse_dedup_bucketed", shape, round(us_b, 1)))
        rows.append(("sparse_dedup_inkernel", shape, round(us_k, 1)))
        out.append(
            f"kernels sparse_dedup K={k}: flat {us_f:.0f} us, bucketed "
            f"{us_b:.0f} us ({us_f / max(us_b, 1e-9):.1f}x), +in-kernel "
            f"fold {us_k:.0f} us (modeled flat "
            f"{exl.dedup_sort_bytes(k)/2**20:.1f} vs bucketed "
            f"{exl.dedup_sort_bytes(k, d)/2**20:.1f} MiB-equiv)")


def bench_scheme_sweep(rows: list, out: list) -> None:
    """Registry-driven embed micro-bench: every *registered* scheme — not a
    hand-kept kind list — gets a ``scheme_embed_<kind>`` row, so registering
    a new scheme (e.g. ``freq``) benches it automatically and
    ``check_regression.py`` can assert the sweep covers the registry."""
    from repro.core.signatures import synthetic_dense_store
    from repro.embed import EmbeddingTable, get_scheme, list_schemes

    vocabs, dim, budget = (24576, 8192), 16, 65536
    shape = f"2048x{dim}@m={budget}"
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, vocabs[0], (2048,), np.int32))
    for kind in list_schemes():
        scheme = get_scheme(kind)
        table = EmbeddingTable(scheme.build_config(vocabs, dim, budget,
                                                   seed=5))
        params = table.init(jax.random.key(5))
        store = synthetic_dense_store(table.config.total_vocab, 16,
                                      max_set=32, seed=2) \
            if scheme.needs_signature_store else None
        bufs = table.make_buffers(store)
        f = jax.jit(lambda p, i, t=table, b=bufs: t.embed(p, b, 0, i))
        us = time_fn(f, params, ids)
        rows.append((f"scheme_embed_{kind}", shape, round(us, 1)))
        out.append(f"kernels scheme_embed[{kind}] {shape}: {us:.0f} us "
                   f"(alpha {table.describe()['expansion_rate']:.1f})")


def run() -> list[str]:
    out = []
    rows = []
    rng = np.random.default_rng(0)

    # measure the 8-device sharded lookup FIRST: it runs in its own
    # subprocess (separate jax runtime), so ordering is free for every
    # other row, but its collective-heavy variants are the rows most
    # sensitive to a machine the parent bench has already saturated —
    # sampling them before the in-process benches keeps the
    # fused/split/replicated ratios comparable to a standalone run
    sharded = bench_sharded_lookup()

    # lma_locations reference at DLRM-batch scale
    p = LMAParams(d=32, m=1 << 21, n_h=4, max_set=32)
    sets = jnp.asarray(rng.integers(0, 2**31, (4096, 32), dtype=np.uint32))
    f = jax.jit(lambda s: lma_ref(p, s))
    us = time_fn(f, sets)
    rows.append(("lma_locations_ref", "4096x32xd32", round(us, 1)))
    out.append(f"kernels lma_locations ref 4096 values: {us:.0f} us "
               f"({4096 * p.n_raw_hashes * 32 / (us/1e6) / 1e9:.1f} Ghash/s)")

    # fused engine vs the split kernel+take path, same 4096x32@m=2^21 shape
    from repro.core.memory import init_memory
    from repro.kernels.fused_embed import ops as fe
    from repro.kernels.lma_locations.ops import lma_locations
    mem = init_memory(jax.random.key(0), p.m, "normal", 0.1)
    gids = jnp.asarray(rng.integers(0, 4096, (4096,), np.int32))
    support = jnp.full((4096,), 32, jnp.int32)
    spec = fe.lma_spec(p)
    split = jax.jit(lambda m_, s: jnp.take(m_, lma_locations(p, s, True),
                                           axis=0))
    us_split = time_fn(split, mem, sets)
    fused = jax.jit(lambda m_, s, g, su: fe.fused_lookup(spec, m_, g, s, su))
    us_fused = time_fn(fused, mem, sets, gids, support)
    rows.append(("lma_split_lookup", "4096x32@m=2^21", round(us_split, 1)))
    rows.append(("lma_fused_lookup", "4096x32@m=2^21", round(us_fused, 1)))
    hbm = modeled_lookup_bytes(4096, 32, p.d)
    out.append(
        f"kernels lma lookup 4096x32@m=2^21: fused {us_fused:.0f} us vs "
        f"split {us_split:.0f} us ({us_split / max(us_fused, 1e-9):.2f}x); "
        f"modeled HBM/lookup {hbm['fused']/2**10:.0f} KiB vs "
        f"{hbm['split']/2**10:.0f} KiB "
        f"(saves 2x the {hbm['location_tensor_bytes']/2**10:.0f} KiB "
        f"[N,d] int32 location tensor)")

    table = jax.random.normal(jax.random.key(0), (65536, 64), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 65536, (2048, 32), dtype=np.int32))
    w = jnp.ones((2048, 32), jnp.float32)
    f = jax.jit(embedding_bag_ref)
    us = time_fn(f, table, ids, w)
    rows.append(("embedding_bag_ref", "2048x32@65536x64", round(us, 1)))
    out.append(f"kernels embedding_bag ref: {us:.0f} us "
               f"({2048*32*64*4/ (us/1e6) / 1e9:.1f} GB/s gathered)")

    feats = jax.random.normal(jax.random.key(1), (2048, 27, 64), jnp.float32)
    f = jax.jit(dot_interaction_ref)
    us = time_fn(f, feats)
    rows.append(("dot_interaction_ref", "2048x27x64", round(us, 1)))
    out.append(f"kernels dot_interaction ref: {us:.0f} us")

    xk = jax.random.normal(jax.random.key(2), (512, 200, 10), jnp.float32)
    x0 = jax.random.normal(jax.random.key(3), (512, 39, 10), jnp.float32)
    wc = jax.random.normal(jax.random.key(4), (200, 200, 39), jnp.float32) * 0.01
    f = jax.jit(cin_ref)
    us = time_fn(f, xk, x0, wc)
    rows.append(("cin_ref", "512x200x39x10", round(us, 1)))
    out.append(f"kernels cin ref: {us:.0f} us")

    upd_bytes = bench_sparse_update(rows, out)
    guard_doc = bench_guarded_step(rows, out)
    tier_doc = bench_tiered(rows, out)
    ckpt_doc = bench_ckpt(rows, out)
    bench_dedup_sort(rows, out)
    bench_scheme_sweep(rows, out)

    if "error" not in sharded:
        shape8 = "4096xd32@m=2^21/8dev"
        rows.append(("sharded_lma_lookup_fused", shape8,
                     sharded["sharded_fused_us"]))
        rows.append(("sharded_lma_lookup_split", shape8,
                     sharded["sharded_split_us"]))
        rows.append(("sharded_lma_lookup_ring", shape8,
                     sharded["sharded_ring_us"]))
        rows.append(("sharded_lma_lookup_all_to_all", shape8,
                     sharded["sharded_all_to_all_us"]))
        rows.append(("sharded_lookup_ring_fused", shape8,
                     sharded["sharded_ring_fused_us"]))
        rows.append(("sharded_lookup_all_to_all_fused", shape8,
                     sharded["sharded_all_to_all_fused_us"]))
        rows.append(("replicated_lma_lookup", "4096xd32@m=2^21/1dev",
                     sharded["replicated_us"]))
        out.append(
            f"kernels sharded_lma_lookup 8dev: psum fused "
            f"{sharded['sharded_fused_us']:.0f} us / split "
            f"{sharded['sharded_split_us']:.0f} us vs ring "
            f"{sharded['sharded_ring_us']:.0f} us (fused-chunked "
            f"{sharded['sharded_ring_fused_us']:.0f} us) vs all_to_all "
            f"{sharded['sharded_all_to_all_us']:.0f} us (fused-chunked "
            f"{sharded['sharded_all_to_all_fused_us']:.0f} us) — best "
            f"{sharded['best_strategy']} at "
            f"{sharded['sharded_over_replicated']:.2f}x replicated "
            f"({sharded['replicated_us']:.0f} us; "
            f"gathered/device {sharded['sharded_gathered_bytes_per_device']/2**10:.0f} KiB, "
            f"resident M/device {sharded['sharded_resident_memory_bytes']/2**20:.0f} MiB "
            f"vs {sharded['replicated_resident_memory_bytes']/2**20:.0f} MiB)")
    else:
        out.append(f"kernels sharded_lma_lookup FAILED: {sharded['error'][:200]}")

    path = save_csv("kernels", ["kernel", "shape", "us"], rows)
    out.append(f"kernels -> {path}")
    # machine-readable ledger next to the CSV: the perf trajectory artifact
    # (benchmarks/check_regression.py diffs fresh runs against this file)
    jpath = os.path.join(ART_DIR, "BENCH_kernels.json")
    with open(jpath, "w") as f:
        json.dump({"rows": [{"kernel": k, "shape": s, "us": u}
                            for k, s, u in rows],
                   "modeled_hbm_bytes_per_lookup": hbm,
                   "modeled_update_bytes_per_step": upd_bytes,
                   "guarded_step_overhead": guard_doc,
                   "tiered": tier_doc,
                   "ckpt": ckpt_doc,
                   "sharded_lookup": sharded}, f, indent=1)
    out.append(f"kernels -> {jpath}")
    return out


if __name__ == "__main__":
    for line in run():
        print(line)
