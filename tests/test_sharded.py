"""Multi-device sharding equivalence, run in a subprocess so this process's
device count stays 1 (the dry-run flag must never leak into other tests).

The subprocess forces 8 host devices, builds a (2, 4) ('data','model') mesh,
and checks that the sharded common-memory lookup is bit-identical to the
single-device oracle — forward AND gradients — both under the auto-resolved
exchange strategy and under the pinned psum oracle with the fused slab
kernel on/off (per-strategy coverage for every registered scheme lives in
tests/test_exchange.py).
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.allocation import LMAParams, alloc_lma, alloc_hashed_elem
from repro.core.memory import init_memory, lookup
from repro.core.signatures import synthetic_dense_store
from repro.dist.sharded_memory import sharded_hashed_lookup, sharded_lma_lookup
from repro.dist.context import use_mesh
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 8, jax.devices()
mesh = make_mesh((2, 4), ("data", "model"))

M_BUDGET = 4096            # divisible by model axis 4
N_VALUES = 512             # divisible by 4 (dense store rows shard over model)
D = 16

lma = LMAParams(d=D, m=M_BUDGET, n_h=2, max_set=16, seed=7)
store = synthetic_dense_store(N_VALUES, n_clusters=8, max_set=16, seed=1)
mem = init_memory(jax.random.key(0), M_BUDGET, "normal", 0.1)
rng = np.random.default_rng(0)
gids = jnp.asarray(rng.integers(0, N_VALUES, (64,), dtype=np.int32))

# ---- oracle (single device, no mesh)
loc = alloc_lma(lma, store, gids)
want = lookup(mem, loc)

def sharded(mem_):
    return sharded_lma_lookup(mem_, store.sets, store.lengths, gids, lma,
                              mesh, ("data",))

with use_mesh(mesh):
    got = sharded(mem)
np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
print("lma forward OK")

# ---- gradients: scatter-add onto the memory must match the oracle transpose
cot = jnp.asarray(rng.normal(0, 1, want.shape).astype(np.float32))

def loss_oracle(m):
    return jnp.vdot(lookup(m, loc), cot)

def loss_sharded(m):
    with use_mesh(mesh):
        return jnp.vdot(sharded(m), cot)

g_want = jax.grad(loss_oracle)(mem)
g_got = jax.grad(loss_sharded)(mem)
np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                           rtol=1e-6, atol=1e-6)
print("lma grad OK")

# ---- hashed fallback path
for kind in ("hashed_elem", "hashed_row"):
    from repro.core.allocation import alloc_hashed_row
    alloc = alloc_hashed_elem if kind == "hashed_elem" else alloc_hashed_row
    loc_h = alloc(gids, D, M_BUDGET, 3)
    want_h = lookup(mem, loc_h)
    with use_mesh(mesh):
        got_h = sharded_hashed_lookup(mem, gids, D, M_BUDGET, 3, mesh,
                                      ("data",), kind=kind)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    print(f"{kind} forward OK")

# ---- 2D input batch (leading axis dp-sharded, trailing replicated)
gids2 = jnp.asarray(rng.integers(0, N_VALUES, (16, 4), dtype=np.int32))
loc2 = alloc_lma(lma, store, gids2.reshape(-1))
want2 = lookup(mem, loc2).reshape(16, 4, D)
with use_mesh(mesh):
    got2 = sharded_lma_lookup(mem, store.sets, store.lengths, gids2, lma,
                              mesh, ("data",))
np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))
print("2d batch OK")

# ---- multi-pod mesh (pod axis joins the dp set)
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
with use_mesh(mesh3):
    got3 = sharded_lma_lookup(mem, store.sets, store.lengths, gids, lma,
                              mesh3, ("pod", "data"))
np.testing.assert_array_equal(np.asarray(got3), np.asarray(want))
print("multi-pod OK")

# ---- fused per-shard gather under the pinned psum strategy: the psum body
# must actually run the fused slab kernel (slab fits VMEM budget), and
# flipping to the legacy split (alloc + local_gather_psum) path must not
# change a single bit — both equal the single-device oracle computed above.
# (The unpinned calls above exercise whatever resolve_exchange picks — ring
# at this shape — so oracle equality covers the auto path too.)
import repro.kernels.fused_embed.ops as feops
from repro.dist.sharded_memory import _fused_slab
assert feops.fused_enabled()
assert _fused_slab(mem[: M_BUDGET // 4])

def sharded_psum(mem_):
    return sharded_lma_lookup(mem_, store.sets, store.lengths, gids, lma,
                              mesh, ("data",), exchange="psum")

def loss_psum(m):
    with use_mesh(mesh):
        return jnp.vdot(sharded_psum(m), cot)

with use_mesh(mesh):
    got_fused = sharded_psum(mem)
g_fused = jax.grad(loss_psum)(mem)
feops.ENABLED = False
try:
    with use_mesh(mesh):
        got_split = sharded_psum(mem)
    g_split = jax.grad(loss_psum)(mem)
finally:
    feops.ENABLED = True
np.testing.assert_array_equal(np.asarray(got_fused), np.asarray(want))
np.testing.assert_array_equal(np.asarray(got_split), np.asarray(got_fused))
np.testing.assert_allclose(np.asarray(g_split), np.asarray(g_fused),
                           rtol=1e-6, atol=1e-6)
np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_got),
                           rtol=1e-6, atol=1e-6)
for kind in ("hashed_elem", "hashed_row"):
    alloc = alloc_hashed_elem if kind == "hashed_elem" else alloc_hashed_row
    want_h = lookup(mem, alloc(gids, D, M_BUDGET, 3))
    feops.ENABLED = False
    try:
        with use_mesh(mesh):
            split_h = sharded_hashed_lookup(mem, gids, D, M_BUDGET, 3, mesh,
                                            ("data",), kind=kind,
                                            exchange="psum")
    finally:
        feops.ENABLED = True
    with use_mesh(mesh):
        fused_h = sharded_hashed_lookup(mem, gids, D, M_BUDGET, 3, mesh,
                                        ("data",), kind=kind,
                                        exchange="psum")
    np.testing.assert_array_equal(np.asarray(fused_h), np.asarray(want_h))
    np.testing.assert_array_equal(np.asarray(fused_h), np.asarray(split_h))
print("fused-vs-split slab gather OK")

print("ALL_SHARDED_CHECKS_PASSED")
"""


FLASH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp

from repro.dist.flash_decode import sharded_flash_decode
from repro.launch.mesh import make_mesh
from repro.nn.attention import blocked_attention, quantize_kv, dequantize_kv

assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))

B, L, KV, G, hd = 4, 64, 2, 3, 16
H = KV * G
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(0, 1, (B, 1, H, hd)).astype(np.float32))
kc = jnp.asarray(rng.normal(0, 1, (B, L, KV, hd)).astype(np.float32))
vc = jnp.asarray(rng.normal(0, 1, (B, L, KV, hd)).astype(np.float32))
kn = jnp.asarray(rng.normal(0, 1, (B, 1, KV, hd)).astype(np.float32))
vn = jnp.asarray(rng.normal(0, 1, (B, 1, KV, hd)).astype(np.float32))
clen = jnp.asarray(37, jnp.int32)   # mid-cache write position
sm = 1.0 / np.sqrt(hd)

# oracle: single-device dynamic update + blocked attention
k_ref = jax.lax.dynamic_update_slice_in_dim(kc, kn, 37, axis=1)
v_ref = jax.lax.dynamic_update_slice_in_dim(vc, vn, 37, axis=1)
o_ref = blocked_attention(
    q, k_ref, v_ref, causal=False,
    q_positions=jnp.asarray([37], jnp.int32),
    kv_positions=jnp.arange(L, dtype=jnp.int32),
    kv_valid_len=clen + 1, block=16)

o, k2, v2 = sharded_flash_decode(q, kc, vc, kn, vn, clen, sm_scale=sm,
                                 mesh=mesh, dp_axes=("data",))
np.testing.assert_array_equal(np.asarray(k2), np.asarray(k_ref))
np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_ref))
np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                           rtol=2e-5, atol=2e-5)
print("flash float OK")

# int8 path: quantize cache + new entries; compare against dequant oracle
kq, ks = quantize_kv(kc)
vq, vs = quantize_kv(vc)
knq, kns = quantize_kv(kn)
vnq, vns = quantize_kv(vn)
o_q, k3, v3, ks3, vs3 = sharded_flash_decode(
    q, kq, vq, knq, vnq, clen, sm_scale=sm, mesh=mesh, dp_axes=("data",),
    k_scale=ks, v_scale=vs, k_scale_new=kns, v_scale_new=vns)
k_deq = dequantize_kv(k3, ks3, jnp.float32)
o_deq_ref = blocked_attention(
    q, k_deq, dequantize_kv(v3, vs3, jnp.float32), causal=False,
    q_positions=jnp.asarray([37], jnp.int32),
    kv_positions=jnp.arange(L, dtype=jnp.int32),
    kv_valid_len=clen + 1, block=16)
np.testing.assert_allclose(np.asarray(o_q), np.asarray(o_deq_ref),
                           rtol=2e-4, atol=2e-4)
# and the quantized result tracks the float result at int8 tolerance
np.testing.assert_allclose(np.asarray(o_q), np.asarray(o_ref),
                           rtol=0.12, atol=0.12)
print("flash int8 OK")

# B=1: cache length spreads over ALL axes (idle dp joins 'model')
q1, k1, v1 = q[:1], kc[:1], vc[:1]
o1, *_ = sharded_flash_decode(q1, k1, v1, kn[:1], vn[:1], clen, sm_scale=sm,
                              mesh=mesh, dp_axes=("data",))
np.testing.assert_allclose(np.asarray(o1), np.asarray(o_ref[:1]),
                           rtol=2e-5, atol=2e-5)
print("flash B=1 full-mesh OK")

print("ALL_FLASH_CHECKS_PASSED")
"""


def _run_sub(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=env, timeout=600)


@pytest.mark.slow
def test_sharded_lookup_equivalence_8dev():
    r = _run_sub(SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_SHARDED_CHECKS_PASSED" in r.stdout


@pytest.mark.slow
def test_sharded_flash_decode_equivalence_8dev():
    r = _run_sub(FLASH_SCRIPT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_FLASH_CHECKS_PASSED" in r.stdout
