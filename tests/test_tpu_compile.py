"""AOT compiles of the chip path for a described v5e:2x2 topology.

Nothing runs and no chip is attached: the installed TPU compiler compiles
for the described devices, and refuses what the chip's compiler would
refuse (a kernel Mosaic cannot lower, a program over the chip's memory).
Each test asks the dispatch rule about a TPU (``repro.kernels.dispatch``)
because ``jax.default_backend()`` here is the CPU.

The topology is described in a module fixture, never while a module is
imported: only the worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import ClosedJaxpr
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch

V5E_HBM_BYTES = 16 * 10**9
BATCH = 4096           # the per-chip batch chip_smoke.py trains at
MAX_CONST_BYTES = 2**20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")


def _consts(closed) -> list:
    """Every constant captured by a (closed) jaxpr, nested jaxprs included."""
    out = list(closed.consts)
    for eqn in closed.jaxpr.eqns:
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                if isinstance(sub, ClosedJaxpr):
                    out += _consts(sub)
    return out


def _max_const_bytes(fn, *args) -> int:
    return max((getattr(c, "nbytes", 0)
                for c in _consts(jax.make_jaxpr(fn)(*args))), default=0)


def _dlrm_rm2():
    from repro.configs.base import get_config
    arch = get_config("dlrm-rm2")
    return arch, arch.make_model(None)


def _one_chip_args(topo, arch, cfg, optimizer):
    from repro.embed import get_scheme
    from repro.launch.steps import store_rows
    from repro.models import recsys
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: recsys.init(jax.random.key(0), cfg)))
    opt_state = jax.tree.map(sds, jax.eval_shape(optimizer.init, params))
    e = cfg.embedding
    bufs = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one)
            for k, (s, d) in get_scheme(e.kind).buffer_specs(
                e, store_rows(e.total_vocab)).items()}
    batch = {"sparse": sds(jax.ShapeDtypeStruct((BATCH, cfg.n_fields),
                                                jnp.int32)),
             "dense": sds(jax.ShapeDtypeStruct((BATCH, cfg.n_dense),
                                               jnp.float32)),
             "label": sds(jax.ShapeDtypeStruct((BATCH,), jnp.float32))}
    return params, opt_state, bufs, batch, one


def test_closed_over_array_is_detected():
    """The constant check sees an array a jitted step closes over."""
    big = jnp.zeros((MAX_CONST_BYTES // 4 + 1,), jnp.float32)
    fn = jax.jit(lambda x: x + big.sum())
    assert _max_const_bytes(fn, 1.0) > MAX_CONST_BYTES
    assert _max_const_bytes(jax.jit(lambda x, b: x + b.sum()), 1.0, big) \
        <= MAX_CONST_BYTES


def test_one_chip_train_step_fits_v5e(topo, on_tpu):
    """The full-width dlrm-rm2 LMA train step the Trainer jits (guarded,
    sparse pool updates, D' store as an argument) compiles for one v5e
    chip within its HBM, with no Pallas kernel and no large constant."""
    from repro.launch import train as launch
    from repro.models import recsys
    from repro.resilience import guard as guard_lib
    arch, cfg = _dlrm_rm2()
    opt = launch.make_optimizer(arch)
    params, opt_state, bufs, batch, one = _one_chip_args(topo, arch, cfg, opt)
    fault = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    step = guard_lib.make_step(
        lambda p, b, bufs: recsys.loss_fn(p, cfg, b, bufs), opt,
        sparse_grads=True, guard=True, donate=True)
    assert _max_const_bytes(step, params, opt_state, batch, fault, bufs) \
        <= MAX_CONST_BYTES
    compiled = step.lower(params, opt_state, batch, fault, bufs).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 5 * 10**9     # pool + state + D'
    assert total < V5E_HBM_BYTES, total
    assert "tpu_custom_call" not in compiled.as_text()


def test_one_chip_eval_forward_compiles(topo, on_tpu):
    from repro.launch import train as launch
    from repro.models import recsys
    arch, cfg = _dlrm_rm2()
    params, _, bufs, batch, _ = _one_chip_args(
        topo, arch, cfg, launch.make_optimizer(arch))
    batch.pop("label")
    fwd = jax.jit(lambda p, b, bufs: recsys.forward(p, cfg, b, bufs))
    assert _max_const_bytes(fwd, params, batch, bufs) <= MAX_CONST_BYTES
    compiled = fwd.lower(params, batch, bufs).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_four_chip_sharded_step_compiles(topo, on_tpu):
    """The (1, 4) ('data', 'model') dlrm-rm2 train cell chip_smoke.py runs
    with --chips 4: pool and D' sharded over 'model', the exchange's
    collectives in the program, no Pallas kernel, within each chip's HBM."""
    from repro.dist.context import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_cell
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    with use_mesh(mesh):
        b = build_cell("dlrm-rm2", "train_batch", mesh, batch=BATCH)
        assert not b.meta["exchange_fused_chunk"]
        fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                     out_shardings=b.out_shardings, donate_argnums=b.donate)
        assert _max_const_bytes(fn, *b.args) <= MAX_CONST_BYTES
        compiled = fn.lower(*b.args).compile()
    mem_sh = b.in_shardings[0]["embedding"]["memory"]
    assert mem_sh.spec[0] == "model"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert any(c in text for c in ("all-reduce", "all-to-all",
                                   "collective-permute"))


def _fused_lookup_compile(one):
    from repro.kernels.fused_embed import kernel as fk
    from repro.kernels.fused_embed.ops import (FusedSpec, _kern_kwargs,
                                               _loc_inputs)
    spec = FusedSpec("lma", 64, 1 << 21, 0, 4, 32, 2, True, True)
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
    fn = jax.jit(lambda m, s, g, u, b: fk.fused_lookup_fwd_pallas(
        "lma", m, _loc_inputs(spec, s, g, u), b,
        **_kern_kwargs(spec, False, 256)))
    fn.lower(S((spec.m,), jnp.float32), S((BATCH, 32), jnp.uint32),
             S((BATCH,), jnp.int32), S((BATCH,), jnp.int32),
             S((1,), jnp.int32)).compile()


def _sparse_adagrad_compile(one):
    from repro.kernels.sparse_update import kernel as sk
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
    k = BATCH * 16
    jax.jit(lambda i, v, a: sk.sparse_adagrad_pallas(
        i, v, a, lr=0.01, eps=1e-8)).lower(
        S((k,), jnp.int32), S((k,), jnp.float32),
        S((1 << 20,), jnp.float32)).compile()


@pytest.mark.parametrize("engine,compile_fn,reason", [
    ("fused_embed", _fused_lookup_compile,
     "Reductions over unsigned integers not implemented"),
    ("sparse_update", _sparse_adagrad_compile, "Only 2D gather is supported"),
])
def test_excluded_engines_are_still_refused(topo, engine, compile_fn,
                                            reason):
    """Each engine the TPU rule excludes is still refused by the v5e
    compiler for the reason the rule names.  When a kernel starts to
    lower, this fails: re-admit it by deleting its TPU_REFUSED entry."""
    assert reason in dispatch.TPU_REFUSED[engine]
    with pytest.raises(Exception, match=reason):
        compile_fn(SingleDeviceSharding(topo.devices[0]))


def test_four_chip_full_tables_step_fits_v5e(topo, on_tpu):
    """The guarded step of dlrm-rm2 with full tables at the published
    widths, as the Trainer runs it on a (1, 4) ('data', 'model') mesh of
    v5e chips at 16,384 examples: the 26 tables padded, lane-packed and
    row-sharded over 'model' with their Adagrad accumulators, updated in
    place.  Each chip's share fits its HBM (and the 5.0 GiB the cell
    allows); no whole-table temporary is made (neither a relayout copy of
    a table nor a dense gradient of the largest one, 0.60 GiB a chip, fits
    under the 1 GiB of temporaries); the lookup's exchange is in the
    program; no large constant is closed over."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import get_config
    from repro.dist import sharding as shd
    from repro.dist.context import use_mesh
    from repro.launch import train as launch
    from repro.launch.mesh import make_mesh
    from repro.models import recsys
    from repro.resilience import guard as guard_lib
    arch = get_config("dlrm-rm2")
    cfg = arch.make_model(None, embedding_kind="full")
    B = 4 * BATCH
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    like = jax.eval_shape(lambda k: recsys.init(k, cfg), jax.random.key(0))
    shapes, shardings = shd.placement(like, mesh, shd.recsys_rules(),
                                      launch.row_tables(cfg))
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shardings)
    opt = launch.make_optimizer(arch)
    # each accumulator is made like its parameter (Trainer: full_like)
    leaves, treedef = jax.tree_util.tree_flatten(
        jax.eval_shape(opt.init, params))
    opt_state = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=p.sharding)
        for s, p in zip(leaves, jax.tree_util.tree_leaves(params))])
    rep = NamedSharding(mesh, P())
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=rep)
    batch = {"sparse": S((B, cfg.n_fields), jnp.int32),
             "dense": S((B, cfg.n_dense), jnp.float32),
             "label": S((B,), jnp.float32)}
    step = guard_lib.make_step(
        lambda p, b, bufs: recsys.loss_fn(p, cfg, b, bufs), opt,
        sparse_grads=True, guard=True, donate=True)
    args = (params, opt_state, batch, S((), jnp.float32), {})
    with use_mesh(mesh):
        assert _max_const_bytes(step, *args) <= MAX_CONST_BYTES
        compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 4 * 10**9    # tables + accumulators
    assert total < 5.0 * 2**30, total
    assert mem.temp_size_in_bytes < 2**30, mem.temp_size_in_bytes
    text = compiled.as_text()
    # each chip's shard of a table over 64 MiB, as the program sees it
    big = {f"f32[{s.shape[0] // 4},{s.shape[1]}]"
           for s in jax.tree_util.tree_leaves(shapes)
           if s.ndim == 2 and s.shape[0] * s.shape[1] > 2**26}
    assert len(big) == 5
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%[\w.-]+ = (f32\[\d+,\d+\])\{[^}]*\} "
                     r"(copy|copy-start|transpose|broadcast)\(", line)
        assert not (m and m.group(1) in big), line[:200]
    assert "all-reduce" in text                     # the psum exchange
    assert "tpu_custom_call" not in text
