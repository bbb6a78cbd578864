"""The TPU dispatch rule and the compile-cache placement, asked directly.

Nothing here touches a device: the rule is asked for a TPU by patching
``repro.kernels.dispatch.platform``, and pool parameters are shapes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import exchange as exl
from repro.embed import EmbeddingConfig, get_scheme, resolve_backend
from repro.embed import backends as bke
from repro.kernels import dispatch
from repro.launch import compile_cache


@pytest.fixture(params=["tpu", "cpu"])
def platform(request, monkeypatch):
    monkeypatch.setattr(dispatch, "platform", lambda: request.param)
    return request.param


def _small_pool(kind="hashed_elem", budget=4096):
    cfg = EmbeddingConfig(kind=kind, vocab_sizes=(512,), dim=8, budget=budget)
    return cfg, {"memory": jax.ShapeDtypeStruct((budget,), jnp.float32)}


def test_resolver_applies_tpu_rule(platform):
    """A pool the fused engine would take on the CPU (interpret mode) goes
    to the split oracle on a TPU, whose compiler refuses the engine."""
    cfg, params = _small_pool()
    want = bke.SPLIT if platform == "tpu" else bke.FUSED
    assert resolve_backend(cfg, params) is want
    assert bke.fused_eligible(cfg, get_scheme(cfg.kind), params) \
        == (platform != "tpu")


def test_exchange_gates_apply_tpu_rule(platform):
    """Whole-slab and chunked engine gates of the sharded exchange: the
    135M-slot production pool chunk-fuses only where Pallas may run."""
    m = 135_053_312
    assert exl.fused_chunk_eligible(m, 4) == (platform != "tpu")
    assert exl.fused_slab_eligible(1 << 20, 4) == (platform != "tpu")


def test_sparse_update_applies_tpu_rule(monkeypatch):
    """On a TPU the sparse optimizer update runs the XLA reference, never
    the refused kernel, for a slab that fits the kernel's VMEM gate."""
    from repro.kernels.sparse_update import kernel as sk
    from repro.kernels.sparse_update import ops
    from repro.kernels.sparse_update import ref

    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")

    def refused(*a, **k):
        raise AssertionError("TPU dispatch reached the refused kernel")

    monkeypatch.setattr(sk, "sparse_adagrad_pallas", refused)
    idx = jnp.asarray([1, 5, 9, 64], jnp.int32)
    val = jnp.asarray([0.5, -1.0, 2.0, 0.0], jnp.float32)
    acc = jnp.zeros((64,), jnp.float32)
    got = ops.sparse_update("adagrad", idx, val, (acc,), lr=0.1, eps=1e-8)
    want = ref.sparse_adagrad_ref(idx, val, acc, lr=0.1, eps=1e-8)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_rule_names_each_excluded_engine(platform):
    lines = dispatch.describe()
    for engine, reason in dispatch.TPU_REFUSED.items():
        (line,) = [ln for ln in lines if ln.startswith(engine)]
        assert (reason in line) == (platform == "tpu"), line
        assert dispatch.pallas_allowed(engine) == (platform != "tpu")


@pytest.fixture
def cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_respects_env(monkeypatch, cache_config, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is left alone: no other path is set
    in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_config):
    """Unset, the cache goes to one fixed directory inside the checkout —
    the same path on every call and in every process, and git-ignored."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.setup_compile_cache() == want
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
