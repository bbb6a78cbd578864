"""Stripe-blocked dense Adagrad for striped pools (repro/optim/sparse.py).

Adagrad on a bucketed stream either gathers, updates and scatters the K
touched slots (the gather/scatter pass) or streams the pool a block of
whole stripes at a time (``stripe_blocked_adagrad``), picked at trace time
by ``stripe_blocked_ok`` from the stream's layout and size.  Covers:

  * parity of the blocked pass with the gather/scatter pass and with dense
    Adagrad on ``densify()``: untouched slots bit-equal, touched slots to
    1e-6 relative, over duplicate-heavy streams, blocks of one, several
    and all stripes (a budget that does not divide the stripe count takes
    the largest divisor under it), both ``initial_acc`` contracts, and a
    guarded step whose cond skips;
  * the routing rule, read from the ``repro.obs`` tallies, including a
    'model' mesh in a subprocess; a transform after the marker raises;
  * the compiled update's memory: no pool-sized temporary, pool and
    accumulator updated in their donated buffers.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.signatures import synthetic_dense_store
from repro.embed import EmbeddingTable, get_scheme
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sp
from repro.resilience import guard as guard_lib

LR, EPS = 0.1, 1e-10
PATHS = ("pool_update.stripe_blocked", "pool_update.gather_scatter")


def _tally() -> dict:
    t = obs.totals()
    return {n: t.get(n, {}).get("calls", 0) for n in PATHS}


def _routed(fn) -> dict:
    """The pool-update tallies ``fn()`` adds."""
    before = _tally()
    fn()
    return {n: c - before[n] for n, c in _tally().items()}


def _stream(rng, d: int, stripe: int, n: int, distinct: int | None = None):
    """A bucketed SparseGrad from [n, d] striped locations; ``distinct``
    slots per stripe makes it duplicate-heavy.  Its values are then
    multiples of 1/8, whose sums are exact in any order: a duplicate
    dropped or counted twice shows, rounding does not."""
    if distinct is None:
        off = rng.integers(0, stripe, (n, d))
        vals = rng.normal(size=(n, d))
    else:
        pick = rng.integers(0, stripe, (distinct, d))
        off = pick[rng.integers(0, distinct, (n, d)), np.arange(d)]
        vals = rng.integers(-8, 9, (n, d)) / 8
    loc = jnp.asarray(np.arange(d)[None, :] * stripe + off, jnp.int32)
    vals = jnp.asarray(vals.astype(np.float32))
    g = sp.from_bucketed_locations(loc, vals, (d * stripe,))
    assert not g.unique and g.buckets == d
    return g


def _pool(rng, m: int) -> jnp.ndarray:
    """Pool values bounded away from 0, so relative gaps are meaningful."""
    return jnp.asarray((rng.uniform(0.5, 1.5, m)
                        * rng.choice([-1.0, 1.0], m)).astype(np.float32))


# ------------------------------------------------------------------ parity

PARITY = {
    # case: (d, stripe, rows, distinct, block budget in stripes, initial_acc)
    "uniform": (8, 512, 64, None, 1, 0.0),
    "duplicate_heavy": (8, 512, 256, 4, 8, 0.0),
    # a budget of 3 stripes that do not divide 8: blocks of 2
    "budget_not_dividing_d": (8, 512, 64, None, 3, 0.0),
    "initial_acc_0.1": (8, 512, 64, 16, 5, 0.1),
}


def _guarded_skip_case():
    """A guarded step whose cond skips leaves params and state bit-equal;
    a clean step matches the dense oracle's step."""
    scheme = get_scheme("lma")
    table = EmbeddingTable(scheme.build_config((512, 256), 8, 4096, seed=3))
    assert table.config.lma.striped
    bufs = table.make_buffers(synthetic_dense_store(
        table.config.total_vocab, 8, max_set=32, seed=2))
    params = {"embedding": table.init(jax.random.key(1))}
    rng = np.random.default_rng(4)
    batch = {"ids": jnp.asarray(rng.integers(0, 256, (48, 2)), jnp.int32),
             "y": jnp.asarray(rng.normal(size=(48, 2, 8)), jnp.float32)}

    def loss_fn(p, b):
        e = table.embed_fields(p["embedding"], bufs, b["ids"])
        loss = jnp.mean((e - b["y"]) ** 2)
        return loss, {"loss": loss}

    opt = opt_lib.multi_transform(
        [(r"(^|/)memory$", sp.sparse_adagrad(LR, eps=EPS))],
        default=opt_lib.adagrad(LR, eps=EPS))
    state = opt.init(params)
    step = guard_lib.make_step(loss_fn, opt, sparse_grads=True, donate=False)
    seen = _routed(lambda: jax.block_until_ready(
        step(params, state, batch, np.float32("nan"))))
    assert seen == {PATHS[0]: 1, PATHS[1]: 0}, seen
    p1, s1, _, _, ok, _ = step(params, state, batch, np.float32("nan"))
    assert not bool(ok)
    for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                    jax.tree_util.tree_leaves((params, state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    p2, s2, _, _, ok, _ = step(params, state, batch, np.float32(1.0))
    assert bool(ok)
    dense = opt_lib.adagrad(LR, eps=EPS)
    dstep = guard_lib.make_step(loss_fn, dense, sparse_grads=False,
                                donate=False)
    p3, s3, *_ = dstep(params, dense.init(params), batch, np.float32(1.0))
    for a, b in zip(jax.tree_util.tree_leaves((p2, s2)),
                    jax.tree_util.tree_leaves((p3, s3))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


@pytest.mark.parametrize("case", list(PARITY) + ["guard_skip"])
def test_stripe_blocked_parity(case, monkeypatch):
    if case == "guard_skip":
        _guarded_skip_case()
        return
    d, stripe, rows, distinct, budget, initial_acc = PARITY[case]
    monkeypatch.setattr(sp, "BLOCK_BYTES", budget * stripe * 4)
    rng = np.random.default_rng(sorted(PARITY).index(case))
    m = d * stripe
    g = _stream(rng, d, stripe, rows, distinct)
    p0 = _pool(rng, m)
    acc0 = sp.sparse_adagrad(LR, EPS, initial_acc).init(p0)
    # a first step leaves the accumulator uneven, as in training
    _, acc0 = sp.stripe_blocked_adagrad(_stream(rng, d, stripe, rows),
                                        acc0, p0, lr=LR, eps=EPS)

    nv, acc_b = sp.stripe_blocked_adagrad(g, acc0, p0, lr=LR, eps=EPS)
    u, (acc_gs,) = sp._leaf_sparse_update("adagrad", g, (acc0,), lr=LR,
                                          eps=EPS)
    p_gs = sp.sparse_apply(p0, u)
    dense = opt_lib.adagrad(LR, eps=EPS, initial_acc=initial_acc)
    ud, acc_d = dense.update({"w": g.densify()}, {"w": acc0}, {"w": p0})
    p_d = opt_lib.apply_updates({"w": p0}, ud)["w"]

    touched = np.zeros(m, bool)
    touched[np.asarray(g.indices)] = True
    if distinct is not None:
        assert touched.sum() <= distinct * d < rows * d   # many duplicates
    got_p, got_a = np.asarray(nv.value), np.asarray(acc_b)
    # untouched: bit-equal, not just close
    np.testing.assert_array_equal(got_p[~touched], np.asarray(p0)[~touched])
    np.testing.assert_array_equal(got_a[~touched],
                                  np.asarray(acc0)[~touched])
    for want_p, want_a in ((p_gs, acc_gs), (p_d, acc_d["w"])):
        np.testing.assert_allclose(got_p[touched],
                                   np.asarray(want_p)[touched], rtol=1e-6)
        np.testing.assert_allclose(got_a[touched],
                                   np.asarray(want_a)[touched], rtol=1e-6)


# ----------------------------------------------------------------- routing

def _route(case: str):
    """-> (update of the pool leaf, tallies its update added)."""
    rng = np.random.default_rng(7)
    d, stripe, rows = 8, 512, 64
    if case == "small_k":                  # K * STREAM_C < m
        stripe = 2 * rows * sp.STREAM_C
    g = _stream(rng, d, stripe, rows)
    if case == "unstriped":                # xdeepfm: m % d != 0, flat dedup
        loc = jnp.asarray(rng.integers(0, d * stripe - 3, (rows, d)),
                          jnp.int32)
        g = sp.from_bucketed_locations(
            loc, jnp.ones((rows, d), jnp.float32), (d * stripe - 3,))
        assert g.unique and not g.buckets
    params = {"memory": _pool(rng, g.dense_shape[0])}
    opt = {"adam": lambda: sp.sparse_rowwise_adam(LR),
           "momentum_sgd": lambda: sp.sparse_sgd(LR, momentum=0.9)}.get(
        case, lambda: sp.sparse_adagrad(LR))()
    state = opt.init(params)
    out = []
    seen = _routed(lambda: out.append(opt.update(
        {"memory": g}, state, None if case == "no_params" else params)))
    return out[0][0]["memory"], seen


@pytest.mark.parametrize("case,path", [
    ("striped_adagrad", "stripe_blocked"),
    ("unstriped", "gather_scatter"),
    ("adam", "gather_scatter"),
    ("momentum_sgd", "gather_scatter"),
    ("small_k", "gather_scatter"),
    ("no_params", "gather_scatter"),
])
def test_pool_update_routing(case, path):
    u, seen = _route(case)
    want = {f"pool_update.{path}": 1}
    assert seen == {n: want.get(n, 0) for n in PATHS}, seen
    assert isinstance(u, sp.NewValue) == (path == "stripe_blocked")


def test_transform_after_new_value_raises():
    rng = np.random.default_rng(8)
    g = _stream(rng, 8, 512, 64)
    params = {"memory": _pool(rng, g.dense_shape[0])}
    for late in (opt_lib.scale(0.5), opt_lib.clip_by_global_norm(1.0)):
        opt = opt_lib.chain(sp.sparse_adagrad(LR), late)
        with pytest.raises(TypeError, match="new value"):
            opt.update({"memory": g}, opt.init(params), params)
    # a transform before the optimizer sees the SparseGrad: allowed
    opt = opt_lib.chain(opt_lib.clip_by_global_norm(1.0),
                        sp.sparse_adagrad(LR))
    u, _ = opt.update({"memory": g}, opt.init(params), params)
    assert isinstance(u["memory"], sp.NewValue)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.optim import optimizers as opt_lib, sparse as sp

d, stripe, rows = 8, 512, 64
rng = np.random.default_rng(0)
loc = jnp.asarray(np.arange(d)[None, :] * stripe
                  + rng.integers(0, stripe, (rows, d)), jnp.int32)
vals = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
p = {"memory": jnp.asarray(rng.normal(size=d * stripe).astype(np.float32))}
opt = sp.sparse_adagrad(0.1)
out = {}
for shape in ((1, 4), (4, 1)):
    def step(p, s):                      # a fresh trace under each mesh
        g = sp.from_bucketed_locations(loc, vals, (d * stripe,))
        u, s = opt.update({"memory": g}, s, p)
        return opt_lib.apply_updates(p, u), s

    with use_mesh(make_mesh(shape, ("data", "model"))):
        obs.reset()
        new, _ = jax.jit(step)(p, opt.init(p))
        jax.block_until_ready(new)
        t = obs.totals()
        out[shape] = tuple(t.get(n, {}).get("calls", 0) for n in
                           ("pool_update.stripe_blocked",
                            "pool_update.gather_scatter"))
    out[shape] = out[shape] + (np.asarray(new["memory"]),)
assert out[(1, 4)][:2] == (0, 1), out[(1, 4)][:2]   # 'model' axis: slabs
assert out[(4, 1)][:2] == (1, 0), out[(4, 1)][:2]   # data only: blocked
np.testing.assert_allclose(out[(1, 4)][2], out[(4, 1)][2], rtol=1e-6)
print("MESH OK")
"""


def test_model_mesh_keeps_gather_scatter():
    """A 'model' axis keeps the sharded slab update; a data-only mesh
    streams the pool, and both give the same pool."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MESH OK" in r.stdout


# ------------------------------------------------------------------ memory

def test_blocked_update_has_no_pool_sized_temporary(monkeypatch):
    """The update as the guarded step runs it (``opt.update`` then
    ``apply_updates``, params and state donated and returned in that
    order): temporaries stay within one block plus O(K), and pool and
    accumulator are updated in their donated buffers."""
    d, stripe, rows = 16, 8192, 64
    m, block = d * stripe, 2 * stripe * 4
    monkeypatch.setattr(sp, "BLOCK_BYTES", block)
    g = _stream(np.random.default_rng(9), d, stripe, rows)
    k = int(g.indices.shape[0])
    opt = sp.sparse_adagrad(LR)
    params = {"memory": jnp.zeros((m,), jnp.float32)}

    def update(p, s, g):
        u, s = opt.update(g, s, p)
        return opt_lib.apply_updates(p, u), s

    seen = {}

    def compile_():
        seen["c"] = jax.jit(update, donate_argnums=(0, 1)).lower(
            params, opt.init(params), {"memory": g}).compile()

    assert _routed(compile_)[PATHS[0]] == 1
    mem = seen["c"].memory_analysis()
    assert mem.temp_size_in_bytes <= block + 16 * k, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * m * 4
