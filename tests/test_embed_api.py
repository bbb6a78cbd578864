"""The repro.embed scheme registry + EmbeddingTable facade.

API-stability contract: ``tests/golden/embed_api_golden.json`` was generated
by the PRE-refactor ``core.embedding`` implementation (same seeds); the new
registry-dispatched API must reproduce its param/buffer tree structure, leaf
shapes, AND leaf/output bytes exactly, and a PR-2-era checkpoint
(``tests/golden/pr2_checkpoint``) must restore through CheckpointManager
unchanged.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.embed as E
from repro.core.allocation import LMAParams
from repro.core.memory import lookup
from repro.core.signatures import synthetic_dense_store
from repro.embed import (EmbeddingConfig, EmbeddingTable, get_scheme,
                         list_schemes, register_scheme, resolve_backend)
from repro.embed import backends as bke

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "embed_api_golden.json")
PR2_CKPT = os.path.join(os.path.dirname(__file__), "golden", "pr2_checkpoint")

SIX_KINDS = ("full", "hashed_elem", "hashed_row", "qr", "lma", "md")


def _sha(a) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def _golden():
    with open(GOLDEN) as f:
        return json.load(f)


def _golden_cfg(g, kind) -> EmbeddingConfig:
    base = dict(kind=kind, vocab_sizes=tuple(g["vocab_sizes"]), dim=g["dim"])
    if kind in ("hashed_elem", "hashed_row", "qr", "lma"):
        base["budget"] = g["budget"]
    if kind == "lma":
        base["lma"] = LMAParams(d=g["dim"], m=g["budget"],
                                n_h=g["lma"]["n_h"],
                                max_set=g["lma"]["max_set"])
    if kind == "md":
        base["md_dims"] = tuple(g["md_dims"])
    return EmbeddingConfig(**base)


def _golden_buffers(table: EmbeddingTable):
    if table.config.kind != "lma":
        return {}
    store = synthetic_dense_store(table.config.total_vocab, 12,
                                  max_set=table.config.lma.max_set, seed=1)
    return table.make_buffers(store)


def _golden_ids(g):
    rng = np.random.default_rng(g["ids_seed"])
    V = g["vocab_sizes"]
    ids2 = np.stack([rng.integers(0, v, 8) for v in V], 1).astype(np.int32)
    bag_ids = rng.integers(0, V[0], (6, 9)).astype(np.int32)
    bag_mask = rng.random((6, 9)) < 0.6
    return ids2, bag_ids, bag_mask


# ----------------------------------------------------- golden-pytree contract

@pytest.mark.parametrize("kind", SIX_KINDS)
def test_init_matches_pre_refactor_golden(kind):
    """EmbeddingTable.init(key) == pre-refactor init_embedding/make_buffers:
    identical key sets, leaf shapes, dtypes, and bytes."""
    g = _golden()
    gk = g["kinds"][kind]
    table = EmbeddingTable(_golden_cfg(g, kind))
    params = table.init(jax.random.key(0))
    bufs = _golden_buffers(table)
    assert sorted(params) == sorted(gk["params"])
    assert sorted(bufs) == sorted(gk["buffers"])
    for name, info in gk["params"].items():
        a = np.asarray(params[name])
        assert list(a.shape) == info["shape"], (kind, name)
        assert str(a.dtype) == info["dtype"], (kind, name)
        assert _sha(a) == info["sha256"], (kind, name, "param bytes changed")
    for name, info in gk["buffers"].items():
        a = np.asarray(bufs[name])
        assert list(a.shape) == info["shape"], (kind, name)
        assert _sha(a) == info["sha256"], (kind, name, "buffer bytes changed")
    assert table.param_count == gk["param_count"]


@pytest.mark.parametrize("kind", SIX_KINDS)
def test_outputs_match_pre_refactor_golden(kind):
    """embed / embed_fields / embed_bag bytes == the pre-refactor dispatch
    (including fused-engine routing where eligible)."""
    g = _golden()
    gk = g["kinds"][kind]
    table = EmbeddingTable(_golden_cfg(g, kind))
    params = table.init(jax.random.key(0))
    bufs = _golden_buffers(table)
    ids2, bag_ids, bag_mask = _golden_ids(g)
    assert _sha(table.embed(params, bufs, 0, jnp.asarray(ids2[:, 0]))) \
        == gk["embed_sha256"]
    assert _sha(table.embed_fields(params, bufs, jnp.asarray(ids2))) \
        == gk["embed_fields_sha256"]
    assert _sha(table.embed_bag(params, bufs, 0, jnp.asarray(bag_ids),
                                jnp.asarray(bag_mask), "sum")) \
        == gk["embed_bag_sum_sha256"]
    assert _sha(table.embed_bag(params, bufs, 0, jnp.asarray(bag_ids),
                                jnp.asarray(bag_mask), "mean")) \
        == gk["embed_bag_mean_sha256"]


def test_pr2_checkpoint_restores_unchanged():
    """A checkpoint written by the PR-2-era code restores through
    CheckpointManager and matches a fresh EmbeddingTable.init bit-for-bit
    (param pytree key names are a stable contract)."""
    from repro.checkpoint.manager import CheckpointManager
    g = _golden()
    mgr = CheckpointManager(PR2_CKPT)
    step, tree = mgr.restore()
    assert step == 60
    table = EmbeddingTable(_golden_cfg(g, "lma"))
    # the checkpoint was written under the PRNG mode of its time: jax
    # flipped jax_threefry_partitionable on by default in 0.5, which
    # changes every jax.random draw (the pool init) but not the lookup
    with jax.threefry_partitionable(False):
        fresh = table.init(jax.random.key(0))
    assert sorted(tree["params"]["embedding"]) == sorted(fresh)
    for k in fresh:
        np.testing.assert_array_equal(np.asarray(tree["params"]["embedding"][k]),
                                      np.asarray(fresh[k]))
    bufs = _golden_buffers(table)
    for k in bufs:
        np.testing.assert_array_equal(np.asarray(tree["buffers"][k]),
                                      np.asarray(bufs[k]))
    # optimizer-moment tree mirrors the param tree (same suffixes)
    assert sorted(tree["opt"][0]["mu"]["embedding"]) == sorted(fresh)


# -------------------------------------------------------- registry / surface

def test_public_surface_resolves():
    for name in E.__all__:
        assert getattr(E, name, None) is not None, name


def test_every_scheme_describe_round_trips():
    """describe() must be JSON-serializable with the core keys present and
    consistent (the dryrun/bench introspection contract)."""
    for kind in list_schemes():
        cfg = get_scheme(kind).build_config((512, 256), 8, 4096)
        d = EmbeddingTable(cfg).describe()
        back = json.loads(json.dumps(d))
        assert back == d, kind
        for key in ("kind", "family", "param_count", "expansion_rate",
                    "dim", "n_tables", "total_vocab"):
            assert key in back, (kind, key)
        assert back["kind"] == kind
        assert back["family"] in ("memory", "table")
        assert back["param_count"] == cfg.param_count()


def test_every_scheme_builds_and_embeds():
    """Registry-driven config -> init -> embed for every registered scheme:
    the path embedding_of_kind and the bench sweep rely on."""
    for kind in list_schemes():
        scheme = get_scheme(kind)
        cfg = scheme.build_config((512, 256), 8, 4096)
        table = EmbeddingTable(cfg)
        params = table.init(jax.random.key(1))
        store = synthetic_dense_store(cfg.total_vocab, 8, max_set=32, seed=1) \
            if scheme.needs_signature_store else None
        bufs = table.make_buffers(store)
        out = table.embed(params, bufs, 0, jnp.asarray([0, 1, 511]))
        assert out.shape == (3, 8), kind
        assert np.isfinite(np.asarray(out)).all(), kind
        n = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(params))
        assert n == table.param_count, kind


def test_unknown_scheme_error_lists_registered():
    with pytest.raises(KeyError, match="freq"):
        get_scheme("nope")


def test_register_scheme_requires_kind():
    with pytest.raises(TypeError):
        @register_scheme
        class Bad(E.Scheme):
            pass


def test_freq_registered_from_its_own_module():
    """The extensibility proof: the freq scheme lives outside the dispatch
    code — repro/embed/table.py, backends.py, and the built-in schemes.py
    contain zero freq logic (the registry only imports the module for
    discovery, like configs.base does for arch configs)."""
    import repro.embed.freq as freq_mod
    scheme = get_scheme("freq")
    assert type(scheme).__module__ == "repro.embed.freq"
    src = os.path.dirname(freq_mod.__file__)
    for core in ("table.py", "backends.py", "schemes.py"):
        assert "freq" not in open(os.path.join(src, core)).read(), core


# ----------------------------------------------------------- backend resolver

def _mem_cfg(kind="hashed_elem", budget=4096):
    return EmbeddingConfig(kind=kind, vocab_sizes=(512,), dim=8, budget=budget)


def test_resolver_split_when_engine_disabled():
    from repro.kernels.fused_embed import ops as fe
    cfg = _mem_cfg()
    params = EmbeddingTable(cfg).init(jax.random.key(0))
    old = fe.ENABLED
    fe.ENABLED = False
    try:
        assert resolve_backend(cfg, params) is bke.SPLIT
    finally:
        fe.ENABLED = old


def test_resolver_fused_when_eligible():
    cfg = _mem_cfg()
    params = EmbeddingTable(cfg).init(jax.random.key(0))
    assert resolve_backend(cfg, params) is bke.FUSED


def test_resolver_fused_rejects_pool_size_mismatch():
    """The engine indexes mod the spec's m: a truncated pool must fall back."""
    cfg = _mem_cfg()
    params = {"memory": jnp.zeros((cfg.budget - 1,), jnp.float32)}
    assert resolve_backend(cfg, params) is bke.SPLIT


def test_resolver_sharded_under_mesh():
    from repro.dist.context import use_mesh
    from repro.launch.mesh import make_mesh
    cfg = _mem_cfg()
    params = EmbeddingTable(cfg).init(jax.random.key(0))
    mesh = make_mesh((1,), ("data",))
    with use_mesh(mesh):
        b = resolve_backend(cfg, params)
    assert isinstance(b, bke.ShardedBackend)


def test_resolver_none_for_table_family():
    cfg = EmbeddingConfig(kind="full", vocab_sizes=(64,), dim=8)
    params = EmbeddingTable(cfg).init(jax.random.key(0))
    assert resolve_backend(cfg, params) is None


def test_freq_never_fused():
    """freq publishes no FusedSpec: the resolver must pick the split oracle
    even at engine-friendly pool sizes."""
    cfg = _mem_cfg("freq")
    params = EmbeddingTable(cfg).init(jax.random.key(0))
    assert resolve_backend(cfg, params) is bke.SPLIT


# ------------------------------------------- satellite: lma init scale (Thm 2)

def test_lma_bernoulli_default_init_is_unit_scale():
    """Theorem 2's init: raw +/-1 entries (variance 1) when init_scale is
    None; the 1/sqrt(d) activation scale applies to the normal init only."""
    cfg = EmbeddingConfig(kind="lma", vocab_sizes=(512,), dim=16, budget=8192,
                          lma=LMAParams(d=16, m=8192, n_h=2, max_set=16),
                          memory_init="bernoulli")
    mem = np.asarray(EmbeddingTable(cfg).init(jax.random.key(0))["memory"])
    assert set(np.unique(mem)) == {-1.0, 1.0}
    assert mem.var() == pytest.approx(1.0, abs=0.05)

    cfg_n = EmbeddingConfig(kind="lma", vocab_sizes=(512,), dim=16,
                            budget=8192,
                            lma=LMAParams(d=16, m=8192, n_h=2, max_set=16),
                            memory_init="normal")
    mem_n = np.asarray(EmbeddingTable(cfg_n).init(jax.random.key(0))["memory"])
    assert mem_n.std() == pytest.approx(1.0 / np.sqrt(16), rel=0.1)


def test_lma_training_config_pins_activation_scale():
    """embedding_of_kind('lma', ...) keeps the explicit 1/sqrt(d) training
    scale (end-to-end conditioning unchanged vs the seed configs)."""
    from repro.configs._recsys_common import lma_embedding
    cfg = lma_embedding((512, 256), 16, expansion=4.0)
    assert cfg.memory_init == "bernoulli"
    assert cfg.init_scale == pytest.approx(1.0 / np.sqrt(16))
    mem = np.asarray(EmbeddingTable(cfg).init(jax.random.key(0))["memory"])
    assert mem.std() == pytest.approx(1.0 / np.sqrt(16), rel=0.05)


# ------------------------------------- satellite: honest expansion_rate alpha

def test_expansion_rate_uses_param_count_for_qr_md():
    g = _golden()
    for kind in ("qr", "md"):
        cfg = _golden_cfg(g, kind)
        expect = cfg.total_vocab * cfg.dim / cfg.param_count()
        assert cfg.expansion_rate == pytest.approx(expect), kind
    # qr's real footprint is below the nominal budget -> alpha must be HIGHER
    # than the old budget-based report (no more overstated compression)
    qr = _golden_cfg(g, "qr")
    assert qr.param_count() < qr.budget
    assert qr.expansion_rate > qr.total_vocab * qr.dim / qr.budget


def test_expansion_rate_budget_kinds_unchanged():
    g = _golden()
    for kind in ("hashed_elem", "hashed_row", "lma"):
        cfg = _golden_cfg(g, kind)
        assert cfg.expansion_rate == pytest.approx(
            cfg.total_vocab * cfg.dim / cfg.budget), kind
    assert _golden_cfg(g, "full").expansion_rate == pytest.approx(1.0)


# ------------------------------------------------------------ freq scheme

def _freq_cfg(budget=2048, hot_k=32, dim=8):
    return EmbeddingConfig(kind="freq", vocab_sizes=(300, 200), dim=dim,
                           budget=budget, seed=3,
                           options=(("hot_k", hot_k),))


def test_freq_hot_ids_get_dedicated_rows():
    cfg = _freq_cfg()
    scheme = get_scheme("freq")
    bufs = scheme.make_buffers(cfg)
    hot = np.asarray(bufs["freq_hot_ids"])
    np.testing.assert_array_equal(hot, np.arange(32))   # default head
    loc = np.asarray(scheme.locations(cfg, bufs, jnp.asarray(hot)))
    # rank r owns slots [r*d, (r+1)*d): collision-free, order-preserving
    want = hot[:, None] * cfg.dim + np.arange(cfg.dim)[None, :]
    np.testing.assert_array_equal(loc, want)


def test_freq_tail_ids_hash_into_tail_region():
    cfg = _freq_cfg()
    scheme = get_scheme("freq")
    bufs = scheme.make_buffers(cfg)
    tail_ids = jnp.asarray(np.arange(32, 500, dtype=np.int32))
    loc = np.asarray(scheme.locations(cfg, bufs, tail_ids))
    assert (loc >= 32 * cfg.dim).all()                   # never in the hot tier
    assert (loc < cfg.budget).all()
    # row-hashed: all d lanes of one id live in one contiguous row
    rows = (loc - 32 * cfg.dim) // cfg.dim
    assert (rows == rows[:, :1]).all()


def test_freq_counts_select_topk():
    cfg = _freq_cfg(hot_k=4)
    scheme = get_scheme("freq")
    counts = np.zeros(cfg.total_vocab, np.int64)
    counts[[7, 123, 400, 9]] = [100, 90, 80, 70]
    bufs = scheme.make_buffers(cfg, counts)
    np.testing.assert_array_equal(np.asarray(bufs["freq_hot_ids"]),
                                  [7, 9, 123, 400])


def test_freq_embed_matches_split_oracle():
    """EmbeddingTable.embed == lookup(memory, locations) bit-for-bit (freq
    has no fused path; the resolver must route to the split oracle)."""
    cfg = _freq_cfg()
    table = EmbeddingTable(cfg)
    params = table.init(jax.random.key(2))
    bufs = table.make_buffers()
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 300, (64,), np.int32))
    got = np.asarray(table.embed(params, bufs, 0, ids))
    scheme = get_scheme("freq")
    want = np.asarray(lookup(params["memory"],
                             scheme.locations(cfg, bufs, ids)))
    np.testing.assert_array_equal(got, want)


def test_freq_gradient_flows_and_is_scatter_add():
    cfg = _freq_cfg()
    table = EmbeddingTable(cfg)
    params = table.init(jax.random.key(2))
    bufs = table.make_buffers()
    ids = jnp.asarray([0, 1, 299])

    def loss(p):
        return jnp.sum(table.embed(p, bufs, 0, ids))

    g = np.asarray(jax.grad(loss)(params)["memory"])
    assert g.sum() == pytest.approx(3 * cfg.dim)


def test_freq_in_registry_sweep_list():
    assert "freq" in list_schemes()


def test_freq_build_config_explicit_hot_k_wins():
    """An explicit hot_k kwarg must override a pre-existing options entry
    (cfg.opt returns the first match)."""
    scheme = get_scheme("freq")
    cfg = scheme.build_config((512,), 8, 4096, hot_k=64,
                              options=(("hot_k", 8),))
    assert scheme.hot_k(cfg) == 64


def test_buffer_specs_match_make_buffers():
    """Scheme.buffer_specs (the dryrun spec-only contract) must agree with
    the concrete make_buffers output: same keys, shapes, dtypes."""
    # lma: D' store rows padded to the launcher's row count
    g = _golden()
    lma_cfg = _golden_cfg(g, "lma")
    store = synthetic_dense_store(lma_cfg.total_vocab, 12,
                                  max_set=lma_cfg.lma.max_set, seed=1)
    concrete = get_scheme("lma").make_buffers(lma_cfg, store)
    specs = get_scheme("lma").buffer_specs(lma_cfg, int(store.sets.shape[0]))
    assert sorted(specs) == sorted(concrete)
    for name, (shape, dt) in specs.items():
        assert tuple(concrete[name].shape) == tuple(shape), name
        assert str(concrete[name].dtype) == dt, name
    # freq: hot-id table
    fcfg = _freq_cfg()
    concrete = get_scheme("freq").make_buffers(fcfg)
    specs = get_scheme("freq").buffer_specs(fcfg, 0)
    assert sorted(specs) == sorted(concrete)
    for name, (shape, dt) in specs.items():
        assert tuple(concrete[name].shape) == tuple(shape), name
        assert str(concrete[name].dtype) == dt, name
    # schemes without buffers stay spec-free
    assert get_scheme("full").buffer_specs(_golden_cfg(g, "full"), 0) == {}


def test_buffer_source_declarations():
    """Launchers key data prep on buffer_source; the built-ins declare it."""
    assert get_scheme("lma").buffer_source == "signatures"
    assert get_scheme("lma").needs_signature_store
    assert get_scheme("freq").buffer_source == "id_counts"
    for kind in ("full", "hashed_elem", "hashed_row", "qr", "md"):
        assert get_scheme(kind).buffer_source is None, kind


def test_freq_sharded_generic_path_matches_oracle():
    """Under a (2, 4) mesh the resolver hands freq the *generic*
    mask-local-gather (no bespoke sharded_lookup); forward must stay
    bit-identical to the single-device oracle.  Subprocess keeps this
    process's device count at 1 (same pattern as tests/test_sharded.py)."""
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.memory import lookup
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh
from repro.embed import EmbeddingConfig, EmbeddingTable, get_scheme
from repro.embed import backends as bke

mesh = make_mesh((2, 4), ("data", "model"))
cfg = EmbeddingConfig(kind="freq", vocab_sizes=(300, 200), dim=16,
                      budget=4096, seed=3, options=(("hot_k", 32),))
table = EmbeddingTable(cfg)
params = table.init(jax.random.key(0))
bufs = table.make_buffers()
rng = np.random.default_rng(0)
ids = jnp.asarray(rng.integers(0, 300, (64,), np.int32))
want = np.asarray(table.embed(params, bufs, 0, ids))
with use_mesh(mesh):
    assert isinstance(bke.resolve_backend(cfg, params),
                      bke.ShardedBackend)
    got = np.asarray(table.embed(params, bufs, 0, ids))
np.testing.assert_array_equal(got, want)
print("freq sharded OK")
"""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "freq sharded OK" in r.stdout


def test_freq_trains_on_synthetic_ctr_smoke():
    """End-to-end: the freq scheme drops into the paper's DLRM smoke config
    (registry-driven embedding_of_kind) and a few adagrad steps move the
    loss — zero edits to dispatch code."""
    from repro.configs.lma_dlrm_criteo import make_smoke
    from repro.data.synthetic_ctr import CTRGenerator, CTRSpec
    from repro.models import recsys
    from repro.optim import optimizers as opt_lib

    cfg = make_smoke(embedding_kind="freq")
    assert cfg.embedding.kind == "freq"
    gen = CTRGenerator(CTRSpec(n_fields=cfg.n_fields, n_dense=cfg.n_dense,
                               vocab_sizes=cfg.embedding.vocab_sizes, seed=0))
    params = recsys.init(jax.random.key(0), cfg)
    opt = opt_lib.adagrad(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: recsys.loss_fn(p, cfg, batch, {}), has_aux=True)(params)
        updates, state = opt.update(grads, state, params)
        return opt_lib.apply_updates(params, updates), state, loss

    losses = []
    for i in range(8):
        batch = {k: jnp.asarray(v) for k, v in gen.batch(64, i).items()}
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert min(losses[-3:]) < losses[0], losses
