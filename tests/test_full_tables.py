"""The full baseline's tables on a mesh: lane-packed placement, the sharded
row lookup under every exchange strategy, and row-sparse Adagrad.

Fast tests run on the single CPU device.  The multi-device test runs a
subprocess with 4 forced host devices on a (1, 4) ('data', 'model') mesh,
as ``tests/test_exchange.py`` does.
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
from repro.configs.base import get_config
from repro.dist import sharding as shd
from repro.embed import EmbeddingConfig, EmbeddingTable
from repro.launch.train import make_optimizer, row_tables
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sp


@pytest.mark.parametrize("shape,n,want", [
    ((10131227, 64), 4, (5065616, 128)),    # Criteo's largest field
    ((3, 64), 4, (4, 128)),
    ((97, 16), 4, (16, 128)),
    ((512, 128), 4, (512, 128)),             # no packing at 128 lanes
    ((10, 48), 4, (12, 48)),                 # 48 does not divide 128
])
def test_row_table_shape_pads_and_packs(shape, n, want):
    got = shd.row_table_shape(shape, n)
    assert got == want
    k = got[1] // shape[1]
    assert got[0] * k >= shape[0] and got[0] % n == 0


def _model(kind="full", vocab=(97, 110, 9, 12), d=16):
    cfg = EmbeddingConfig(kind=kind, vocab_sizes=vocab, dim=d)
    table = EmbeddingTable(cfg)
    return cfg, table, table.init(jax.random.key(2))


def _loss(table):
    def loss_fn(p, ids, y):
        e = table.embed_fields(p["embedding"], {}, ids)
        out = jnp.sum(e, axis=(1, 2)) * p["w"]
        return jnp.mean((out - y) ** 2), {}
    return loss_fn


def _batch(step, vocab, B=32, unique=False):
    r = np.random.default_rng(step)
    if unique:      # distinct ids per field: no duplicate rows to sum
        ids = np.stack([r.permutation(v)[:B] for v in vocab], axis=1)
    else:
        ids = np.stack([r.integers(0, v, B) for v in vocab], axis=1)
    return (jnp.asarray(ids, jnp.int32),
            jnp.asarray(r.normal(size=B).astype(np.float32)))


def test_table_lookup_records_rows_and_no_table_gradient():
    """Under the sparse engine each table leaf's gradient is a SparseGrad
    of its rows ([V, d] dense shape) whose densified form equals the dense
    gradient, and the lookup carries the ``table_lookup`` scope."""
    cfg, table, emb = _model()
    params = {"embedding": emb, "w": jnp.float32(0.5)}
    ids, y = _batch(0, cfg.vocab_sizes)
    loss_fn = _loss(table)
    (_, _), gs = sp.sparse_value_and_grad(loss_fn)(params, ids, y)
    (_, _), gd = jax.value_and_grad(loss_fn, has_aux=True)(params, ids, y)
    for t, v in enumerate(cfg.vocab_sizes):
        g = gs["embedding"][f"table_{t}"]
        assert sp.is_sparse(g) and g.dense_shape == (v, cfg.dim)
        np.testing.assert_allclose(np.asarray(g.densify()),
                                   np.asarray(gd["embedding"][f"table_{t}"]),
                                   rtol=1e-6, atol=1e-7)
    text = jax.jit(lambda p: table.embed_fields(p, {}, ids)).lower(
        emb).as_text(debug_info=True)
    assert "table_lookup" in text


def test_row_sparse_adagrad_equals_dense_adagrad_bitwise():
    """Row-sparse Adagrad on ``table_{t}`` leaves (the launcher's routing)
    gives the dense Adagrad update to the bit, on the gradient it was
    handed and over three training steps of distinct ids; each trace
    tallies ``table_update.sparse_rows`` once per table leaf."""
    arch = get_config("dlrm-rm2")
    cfg, table, emb = _model()
    params = {"embedding": emb, "w": jnp.float32(0.5)}
    loss_fn = _loss(table)
    sparse_opt = make_optimizer(arch)
    dense_opt = opt_lib.adagrad(arch.learning_rate)

    # one update from the same SparseGrad
    ids, y = _batch(1, cfg.vocab_sizes)
    _, g = sp.sparse_value_and_grad(loss_fn)(params, ids, y)
    gd = jax.tree_util.tree_map(
        lambda x: x.densify() if sp.is_sparse(x) else x, g,
        is_leaf=sp.is_sparse)
    obs.reset()
    u, _ = sparse_opt.update(g, sparse_opt.init(params), params)
    a = opt_lib.apply_updates(params, u)
    counts = {k: v["calls"] for k, v in obs.totals().items()
              if k.startswith("table_update.")}
    assert counts == {"table_update.sparse_rows": cfg.n_tables}
    ud, _ = dense_opt.update(gd, dense_opt.init(params), params)
    b = opt_lib.apply_updates(params, ud)
    for x, z in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))

    # three steps: the sparse engine and optimizer against dense
    def train(sparse):
        opt = sparse_opt if sparse else dense_opt
        vg = (sp.sparse_value_and_grad(loss_fn) if sparse
              else jax.value_and_grad(loss_fn, has_aux=True))

        @jax.jit
        def step(p, s, ids, y):
            _, g = vg(p, ids, y)
            u, s = opt.update(g, s, p)
            return opt_lib.apply_updates(p, u), s

        p, s = params, opt.init(params)
        for i in range(3):
            p, s = step(p, s, *_batch(10 + i, cfg.vocab_sizes, B=8,
                                      unique=True))
        return p

    for x, z in zip(jax.tree_util.tree_leaves(train(True)),
                    jax.tree_util.tree_leaves(train(False))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


def test_dense_table_gradient_counts_dense_and_matches():
    """A dense gradient of a table leaf (nothing recorded its rows) takes
    the dense Adagrad formula under ``table_update.dense``."""
    arch = get_config("dlrm-rm2")
    opt = make_optimizer(arch)
    p = {"embedding": {"table_0": jnp.ones((8, 4))}}
    g = {"embedding": {"table_0": jnp.full((8, 4), 0.5)}}
    obs.reset()
    u, _ = opt.update(g, opt.init(p), p)
    assert obs.totals()["table_update.dense"]["calls"] == 1
    ud, _ = opt_lib.adagrad(arch.learning_rate).update(
        g, opt_lib.adagrad(arch.learning_rate).init(p), p)
    np.testing.assert_array_equal(np.asarray(u["embedding"]["table_0"]),
                                  np.asarray(ud["embedding"]["table_0"]))


def test_row_tables_only_for_row_schemes():
    arch = get_config("dlrm-rm2")
    assert row_tables(arch.make_smoke(embedding_kind="full")) == shd.ROW_TABLES
    assert row_tables(arch.make_smoke(embedding_kind="md")) is None
    assert row_tables(arch.make_smoke()) is None             # lma


def test_no_mesh_lookup_is_one_take_per_table():
    cfg, table, emb = _model()
    ids, _ = _batch(3, cfg.vocab_sizes)
    got = table.embed_fields(emb, {}, ids)
    want = jnp.stack([jnp.take(emb[f"table_{t}"], ids[:, t], axis=0)
                      for t in range(cfg.n_tables)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from repro import obs
from repro.dist import exchange as exl
from repro.dist import sharding as shd
from repro.dist.context import use_mesh
from repro.dist.sharded_memory import sharded_table_lookup
from repro.embed import EmbeddingConfig, EmbeddingTable
from repro.launch.mesh import parse_mesh
from repro.optim import optimizers as opt_lib
from repro.optim import sparse as sp

assert len(jax.devices()) == 4
mesh = parse_mesh("1x4")
vocab, d, B = (97, 110, 3, 12, 130), 16, 64
cfg = EmbeddingConfig(kind="full", vocab_sizes=vocab, dim=d)
table = EmbeddingTable(cfg)
emb = table.init(jax.random.key(1))
rng = np.random.default_rng(0)
ids = jnp.asarray(np.stack([rng.integers(0, v, B) for v in vocab], 1),
                  jnp.int32)
want = np.asarray(table.embed_fields(emb, {}, ids))        # no mesh

# placed: padded and lane-packed (8 rows of 16 to a 128-lane row)
placed = shd.place({"embedding": emb}, mesh, shd.recsys_rules(),
                   shd.ROW_TABLES)["embedding"]
assert placed["table_0"].shape == (16, 128), placed["table_0"].shape
assert placed["table_0"].sharding.spec[0] == "model"
for name in ("psum", "ring", "all_to_all"):
    exl.FORCED = name
    obs.reset()
    try:
        with use_mesh(mesh):
            got = jax.jit(lambda p, i: table.embed_fields(p, {}, i))(
                placed, ids)
    finally:
        exl.FORCED = None
    np.testing.assert_array_equal(np.asarray(got), want)
    assert obs.totals()[f"table_lookup.{name}"]["calls"] == 1
    print("packed", name, "bitwise OK")

# unpacked tables of rows 'model' divides, through the driver directly
tabs = [jax.random.normal(jax.random.key(t), (8 * (t + 1), 128))
        for t in range(3)]
ids2 = jnp.asarray(np.stack([rng.integers(0, 8 * (t + 1), B)
                             for t in range(3)], 1), jnp.int32)
want2 = np.stack([np.asarray(x)[np.asarray(ids2[:, t])]
                  for t, x in enumerate(tabs)], 1)
for name in ("psum", "ring", "all_to_all"):
    with use_mesh(mesh):
        got = sharded_table_lookup(tabs, ids2, 128, mesh, ("data",),
                                   exchange=name)
    np.testing.assert_array_equal(np.asarray(got), want2)
    print("unpacked", name, "bitwise OK")

# three training steps: sharded, packed, row-sparse vs no-mesh dense
def loss_fn(p, ids, y):
    e = table.embed_fields(p["embedding"], {}, ids)
    return jnp.mean((jnp.sum(e, axis=(1, 2)) * p["w"] - y) ** 2), {}

def batch(s):
    r = np.random.default_rng(100 + s)
    return (jnp.asarray(np.stack([r.integers(0, v, B) for v in vocab], 1),
                        jnp.int32),
            jnp.asarray(r.normal(size=B).astype(np.float32)))

def train(sparse, p, forced=None):
    opt = (opt_lib.multi_transform(
        [(r"table_\d+$", sp.sparse_adagrad(0.1, tables=True))],
        default=opt_lib.adagrad(0.1)) if sparse else opt_lib.adagrad(0.1))
    vg = sp.sparse_value_and_grad(loss_fn) if sparse else \
        jax.value_and_grad(loss_fn, has_aux=True)
    def step(p, s, ids, y):
        _, g = vg(p, ids, y)
        u, s = opt.update(g, s, p)
        return opt_lib.apply_updates(p, u), s
    s = opt.init(p)
    exl.FORCED = forced
    try:
        for i in range(3):
            if sparse:
                with use_mesh(mesh):
                    p, s = jax.jit(step)(p, s, *batch(i))
            else:
                p, s = jax.jit(step)(p, s, *batch(i))
    finally:
        exl.FORCED = None
    return p

dense = train(False, {"embedding": emb, "w": jnp.float32(0.5)})
for forced in ("psum", "ring", "all_to_all"):
    p0 = shd.place({"embedding": emb, "w": jnp.float32(0.5)}, mesh,
                   shd.recsys_rules(), shd.ROW_TABLES)
    got = train(True, p0, forced)
    for t, v in enumerate(vocab):
        a = np.asarray(dense["embedding"][f"table_{t}"])
        b = np.asarray(got["embedding"][f"table_{t}"]).reshape(-1, d)
        np.testing.assert_allclose(b[:v], a, rtol=1e-6, atol=1e-7)
        assert not b[v:].any()
    print("train", forced, "OK")
print("FULL_MESH_OK")
"""


@pytest.mark.slow
def test_sharded_table_lookup_and_training_parity_1x4():
    """On a (1, 4) mesh of forced host devices: the full tables' sharded
    lookup under psum, ring and all_to_all is bitwise the no-mesh
    ``jnp.take`` (lane-packed and unpacked tables), tallying its strategy,
    and three steps of row-sparse Adagrad on the packed, 'model'-sharded
    tables match dense no-mesh Adagrad, padded rows untouched."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("REPRO_DIST_EXCHANGE", None)
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "FULL_MESH_OK" in r.stdout
