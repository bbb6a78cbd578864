"""Flash-decoding with the KV-cache *length* sharded over the mesh.

Decode attends one query against an L-long cache.  Sharding heads over
'model' dies on archs whose head counts don't divide the axis (qwen's 40)
and leaves the B=1 long-context cell unsharded entirely — so instead the
cache LENGTH shards over 'model' plus every dp axis the batch leaves idle
(LM_CACHE_RULES in launch/steps.py).  Each device:

  1. writes the new KV entry in place iff the write position ``cache_len``
     falls inside its length-slab (bit-identical to the single-device
     ``dynamic_update_slice``);
  2. computes online-softmax partials (running max m, normalizer l,
     weighted value accumulator) over its slab only;
  3. merges across slabs by log-sum-exp: ``m* = pmax(m)``,
     ``l* = psum(l * exp(m - m*))``, ``acc* = psum(acc * exp(m - m*))``.

Float and int8-scaled cache paths share the body; int8 slabs are
dequantized locally (same values the oracle dequantizes globally).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

_NEG_INF = -1e30


def _axes_prod(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _plan(mesh, dp_axes, B: int, L: int):
    """-> (batch_axes, seq_axes) or None when L cannot shard.

    Batch takes the dp axes when it divides them; the cache length takes
    'model' plus whatever dp axes the batch left idle (mesh order — the same
    resolution LM_CACHE_RULES produces), falling back to 'model' alone.
    """
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    batch = dp if (_axes_prod(mesh, dp) > 1 and B % _axes_prod(mesh, dp) == 0) \
        else ()
    seq_full = tuple(a for a in mesh.axis_names
                     if a == "model" or (a in dp and a not in batch))
    for seq in (seq_full, ("model",) if "model" in mesh.axis_names else ()):
        if seq and _axes_prod(mesh, seq) > 1 and L % _axes_prod(mesh, seq) == 0:
            return batch, seq
    return None


def _spec(batch_axes, trailing: int):
    b = None if not batch_axes else (
        batch_axes if len(batch_axes) > 1 else batch_axes[0])
    return P(b, *([None] * trailing))


def _seq_spec(batch_axes, seq_axes, trailing: int):
    b = None if not batch_axes else (
        batch_axes if len(batch_axes) > 1 else batch_axes[0])
    s = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    return P(b, s, *([None] * trailing))


def _shard_write(cache_l, new, rel, own):
    """In-place slab write of the length-1 new entry iff this rank owns it."""
    upd = jax.lax.dynamic_update_slice_in_dim(
        cache_l, new.astype(cache_l.dtype), rel, axis=1)
    return jnp.where(own, upd, cache_l)


def sharded_flash_decode(
    q: jax.Array,            # [B, 1, H, hd]
    k_cache: jax.Array,      # [B, L, KV, hd]   float or int8
    v_cache: jax.Array,      # [B, L, KV, vd]
    k_new: jax.Array,        # [B, 1, KV, hd]
    v_new: jax.Array,        # [B, 1, KV, vd]
    cache_len: jax.Array,    # scalar int32: write position; <= it is valid
    *,
    sm_scale: float,
    mesh,
    dp_axes,
    k_scale: jax.Array | None = None,       # [B, L, KV] (int8 path)
    v_scale: jax.Array | None = None,
    k_scale_new: jax.Array | None = None,   # [B, 1, KV]
    v_scale_new: jax.Array | None = None,
):
    """LSE-merged decode attention + in-place KV cache update.

    Returns ``(o, k, v)`` (float cache) or ``(o, k, v, k_scale, v_scale)``
    (int8 cache).  ``o`` [B, 1, H, vd] matches ``blocked_attention`` over the
    updated cache with ``kv_valid_len = cache_len + 1``; the updated caches
    are bit-identical to the single-device ``dynamic_update_slice``.
    """
    B, _, H, hd = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2]
    vd = v_cache.shape[-1]
    G = H // KV
    quant = k_cache.dtype == jnp.int8
    plan = _plan(mesh, dp_axes, B, L)
    if plan is None:
        return _unsharded(q, k_cache, v_cache, k_new, v_new, cache_len,
                          sm_scale, k_scale, v_scale, k_scale_new, v_scale_new)
    batch, seq = plan
    sizes = dict(mesh.shape)
    l_loc = L // _axes_prod(mesh, seq)

    def body(q_l, kc_l, vc_l, kn_l, vn_l, clen, ks_l, vs_l, ksn_l, vsn_l):
        blk = jnp.int32(0)
        for a in seq:
            blk = blk * sizes[a] + jax.lax.axis_index(a)
        lo = blk * l_loc
        pos = clen.astype(jnp.int32)
        # write position clamps to L-1 exactly like the single-device
        # dynamic_update_slice oracle, so a full cache (pos >= L) overwrites
        # the last slot on the last rank instead of silently dropping the
        # entry (exactly one rank owns the clamped position)
        wpos = jnp.clip(pos, 0, jnp.int32(L - 1))
        own = (wpos >= lo) & (wpos < lo + l_loc)
        rel = jnp.clip(wpos - lo, 0, l_loc - 1)
        kc_l = _shard_write(kc_l, kn_l, rel, own)
        vc_l = _shard_write(vc_l, vn_l, rel, own)
        if quant:
            ks_l = _shard_write(ks_l, ksn_l, rel, own)
            vs_l = _shard_write(vs_l, vsn_l, rel, own)
            kf = kc_l.astype(jnp.float32) * ks_l[..., None]
            vf = vc_l.astype(jnp.float32) * vs_l[..., None]
        else:
            kf, vf = kc_l, vc_l

        qr = (q_l.astype(jnp.float32) * sm_scale).reshape(B_l, 1, KV, G, hd)
        s = jnp.einsum("bqKGh,btKh->bKGqt", qr, kf.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        kv_pos = lo + jnp.arange(l_loc, dtype=jnp.int32)
        valid = kv_pos < pos + 1
        s = jnp.where(valid[None, None, None, None, :], s, _NEG_INF)
        m_l = jnp.max(s, axis=-1)                            # [B,KV,G,1]
        p = jnp.exp(s - m_l[..., None])
        l_l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bKGqt,btKd->bKGqd", p, vf.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_l, seq)
        corr = jnp.exp(m_l - m_g)                            # 0 for empty slabs
        l_g = jax.lax.psum(l_l * corr, seq)
        acc_g = jax.lax.psum(acc * corr[..., None], seq)
        o = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        o = jnp.moveaxis(o, 3, 1).reshape(B_l, 1, H, vd).astype(q_l.dtype)
        if quant:
            return o, kc_l, vc_l, ks_l, vs_l
        return o, kc_l, vc_l

    bspec4 = _spec(batch, 3)
    cspec4 = _seq_spec(batch, seq, 2)
    B_l = B // _axes_prod(mesh, batch)
    if quant:
        in_specs = (bspec4, cspec4, cspec4, bspec4, bspec4, P(),
                    _seq_spec(batch, seq, 1), _seq_spec(batch, seq, 1),
                    _spec(batch, 2), _spec(batch, 2))
        out_specs = (bspec4, cspec4, cspec4, _seq_spec(batch, seq, 1),
                     _seq_spec(batch, seq, 1))
        args = (q, k_cache, v_cache, k_new, v_new, cache_len,
                k_scale, v_scale, k_scale_new, v_scale_new)
    else:
        dummy = jnp.zeros((), jnp.float32)  # scale placeholders keep one body
        in_specs = (bspec4, cspec4, cspec4, bspec4, bspec4, P(),
                    P(), P(), P(), P())
        out_specs = (bspec4, cspec4, cspec4)
        args = (q, k_cache, v_cache, k_new, v_new, cache_len,
                dummy, dummy, dummy, dummy)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return fn(*args)


def _unsharded(q, k_cache, v_cache, k_new, v_new, cache_len, sm_scale,
               k_scale, v_scale, k_scale_new, v_scale_new):
    """Single-device fallback (mesh can't shard L): same contract."""
    from repro.nn.attention import blocked_attention, dequantize_kv

    quant = k_cache.dtype == jnp.int8
    L = k_cache.shape[1]
    k = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), cache_len, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), cache_len, axis=1)
    if quant:
        ks = jax.lax.dynamic_update_slice_in_dim(
            k_scale, k_scale_new.astype(jnp.float32), cache_len, axis=1)
        vs = jax.lax.dynamic_update_slice_in_dim(
            v_scale, v_scale_new.astype(jnp.float32), cache_len, axis=1)
        kf = dequantize_kv(k, ks, q.dtype)
        vf = dequantize_kv(v, vs, q.dtype)
    else:
        kf, vf = k, v
    o = blocked_attention(
        q, kf, vf, causal=False,
        q_positions=cache_len.reshape(1).astype(jnp.int32),
        kv_positions=jnp.arange(L, dtype=jnp.int32),
        kv_valid_len=cache_len + 1, sm_scale=sm_scale)
    if quant:
        return o, k, v, ks, vs
    return o, k, v
