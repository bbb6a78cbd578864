"""Mixture-of-Experts FFN with gather-based capacity dispatch.

Dispatch is expressed as dense-shape gather/scatter (top-C tokens per expert by
routing score), not the GShard [T, E, C] one-hot einsum — at 1M tokens x 256
experts the one-hot mask is infeasible, while [E, C] index tensors are tiny and
the expert GEMM is a clean [E, C, d] x [E, d, f] batched matmul on the MXU.
Expert weights are stacked on a leading E axis so the sharding rules can lay
experts over the `model` mesh axis (expert parallelism).

Supports DeepSeek-V3-style (sigmoid router, shared + fine-grained routed experts,
top-8) and Llama4-Scout-style (top-1, 16 experts + shared) through one config.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.modules import glu_ffn, glu_ffn_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per routed expert
    n_experts: int
    top_k: int
    n_shared_experts: int = 0    # shared expert(s) of width n_shared * d_ff
    router: str = "softmax"      # "softmax" | "sigmoid" (DeepSeek-V3)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s = 1.0 / np.sqrt(d)
    p = {
        "router": {"kernel": (jax.random.normal(kr, (d, E)) * s).astype(jnp.float32)},
        "w_gate": (jax.random.normal(kg, (E, d, f)) * s).astype(dtype),
        "w_up": (jax.random.normal(ku, (E, d, f)) * s).astype(dtype),
        "w_down": (jax.random.normal(kd, (E, f, d)) / np.sqrt(f)).astype(dtype),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = glu_ffn_init(ks, d, cfg.n_shared_experts * f, dtype=dtype)
    return p


def moe_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    c = int(np.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)


def _route(router_kernel, cfg: MoEConfig, x):
    """x [T, d] -> (R [T, E] routing weights, aux scalar)."""
    logits = (x.astype(jnp.float32) @ router_kernel)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(scores, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_i, cfg.n_experts, dtype=jnp.float32)
    R = jnp.einsum("tk,tke->te", top_w, onehot)
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    mean_prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    aux = cfg.n_experts * jnp.sum(frac_tokens * mean_prob)
    return R, aux


def _expert_ffn(w_gate, w_up, w_down, xe):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_w_specs(cfg: MoEConfig, mesh):
    """Storage PartitionSpecs of the per-layer expert weights — MUST match the
    lm_rules templates (dist.sharding) so shard_map in_specs equal the stored
    sharding and no resharding happens at the boundary."""
    from repro.dist.sharding import DP, EP, resolve_template
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    sg = resolve_template([[EP, "model", "data"], [DP, "pod", "data"], None],
                          (E, d, f), mesh)
    sd = resolve_template([[EP, "model", "data"], None, [DP, "pod", "data"]],
                          (E, f, d), mesh)
    return sg, sd


def _axes_tuple(spec, i):
    """Mesh axes of spec dim i (specs may omit trailing unsharded dims)."""
    entry = spec[i] if i < len(spec) else None
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def _full_rank(spec, rank):
    entries = list(spec) + [None] * (rank - len(spec))
    return jax.sharding.PartitionSpec(*entries)


def moe_apply_sharded(p: dict, cfg: MoEConfig, x: jax.Array, mesh,
                      dp_axes: tuple[str, ...],
                      full_token_sharding: bool = False,
                      lead: int | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map (the production path).

    Tokens stay sharded over the dp axes.  Expert weights enter the shard_map
    in their ZeRO-3 *storage* sharding (E over ('data','model'), d over 'pod')
    and are all-gathered INSIDE the body down to "experts split over 'model',
    d/f full" — so the shard_map transpose emits reduce-scatters and the
    gradient (and optimizer-state) accumulators stay storage-sharded.  Letting
    GSPMD reshard at the boundary instead materializes the whole stacked
    cotangent at 'model'-only sharding (50+ GiB/device for DeepSeek-V3).

    Per-device flow: route local tokens -> pick my experts' top-C_local tokens
    -> batched expert GEMM -> local scatter-add combine -> psum over 'model'.
    """
    P = jax.sharding.PartitionSpec
    T, d = x.shape
    E = cfg.n_experts
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    M = int(mesh.shape["model"])
    # token sharding ladder: full mesh (dp x model — matches the sequence-
    # parallel residual layout, so prefill/train enter with ZERO reshard;
    # the model-axis gather happens in bf16 inside the body and the output
    # leaves via reduce-scatter) > dp-only > replicated (decode-sized T)
    # full-mesh token sharding is an INFERENCE optimization: in training the
    # per-layer gathered-token residuals dominate backward memory (deepseek
    # train_4k: 23.6 -> 179 GiB/device when enabled there).
    # ``lead``: the caller's [B, S, d] batch dim.  The flat (dp x model)
    # token sharding reshapes back to (B over dp, S over 'model') ONLY when
    # B == dp_size; any other factoring leaves GSPMD a {B-ways, S-ways}
    # layout the residual constraint can't reach without an involuntary
    # full rematerialization of the [B, S, d] stream (20 GiB/device f32 for
    # llama4 prefill_32k@16x16) — fall back to dp-only tokens instead.
    tokens_full = (full_token_sharding
                   and T % (dp_size * M) == 0 and T >= dp_size * M
                   and (lead is None or lead == dp_size))
    tokens_sharded = T % dp_size == 0 and T >= dp_size
    dp = dp_axes if len(dp_axes) != 1 else dp_axes[0]
    if tokens_full:
        x_spec = P((*dp_axes, "model"), None)
    elif tokens_sharded:
        x_spec = P(dp, None)
    else:
        x_spec = P(None, None)
    spec_g, spec_d = _moe_w_specs(cfg, mesh)
    e_axes = _axes_tuple(spec_g, 0)          # E-dim mesh axes (storage)
    gd_axes = _axes_tuple(spec_g, 1)         # d-dim axes of w_gate/w_up
    dd_axes = _axes_tuple(spec_d, 2)         # d-dim axes of w_down
    spec_g, spec_d = _full_rank(spec_g, 3), _full_rank(spec_d, 3)
    e_extra = tuple(a for a in e_axes if a != "model")
    assert e_extra in ((), ("data",)), e_extra
    e_local = E // M                          # experts computed per model rank

    def gather_w(w, dim_axes_pairs):
        for axis, dim in dim_axes_pairs:
            w = jax.lax.all_gather(w, axis, axis=dim, tiled=True)
        return w

    def my_expert_ids(mj):
        if e_extra:  # storage E over (data, model): stride pattern after gather
            D = int(mesh.shape["data"])
            bs = E // (D * M)
            ids = ((jnp.arange(D, dtype=jnp.int32)[:, None] * M + mj) * bs
                   + jnp.arange(bs, dtype=jnp.int32)[None, :])
            return ids.reshape(-1)
        bs = E // M
        return mj * bs + jnp.arange(bs, dtype=jnp.int32)

    def body(router, w_gate, w_up, w_down, x_loc):
        T_loc = x_loc.shape[0]
        mj = jax.lax.axis_index("model") if M > 1 else jnp.int32(0)
        # ZeRO-3 gather: experts end up split over 'model' only, d/f full
        w_gate = gather_w(w_gate, [(a, 1) for a in gd_axes]
                          + [(a, 0) for a in e_extra])
        w_up = gather_w(w_up, [(a, 1) for a in gd_axes]
                        + [(a, 0) for a in e_extra])
        w_down = gather_w(w_down, [(a, 2) for a in dd_axes]
                          + [(a, 0) for a in e_extra])
        if not e_axes:  # replicated storage: compute only my slice
            sl = E // M
            w_gate = jax.lax.dynamic_slice_in_dim(w_gate, mj * sl, sl, 0)
            w_up = jax.lax.dynamic_slice_in_dim(w_up, mj * sl, sl, 0)
            w_down = jax.lax.dynamic_slice_in_dim(w_down, mj * sl, sl, 0)

        if tokens_full:  # gather the model-axis token shards (bf16, in-body)
            x_loc = jax.lax.all_gather(x_loc, "model", axis=0, tiled=True)
            T_loc = x_loc.shape[0]

        R, aux = _route(router, cfg, x_loc)                   # [T_loc, E]
        C = min(moe_capacity(cfg, T_loc), T_loc)
        ids = my_expert_ids(mj)                               # [e_local]
        R_my = jnp.take(R.T, ids, axis=0)                     # [e_local, T_loc]
        pr, tok_idx = jax.lax.top_k(R_my, C)
        keep = (pr > 0.0).astype(pr.dtype)
        xe = jnp.take(x_loc, tok_idx, axis=0)                 # [e_local, C, d]
        ye = _expert_ffn(w_gate, w_up, w_down, xe)
        ye = ye * (pr * keep)[..., None].astype(ye.dtype)
        out = jnp.zeros((T_loc, d), ye.dtype).at[
            tok_idx.reshape(-1)].add(ye.reshape(-1, d), mode="drop")
        if M > 1:
            if tokens_full:
                # combine expert partials AND return to the (dp x model)
                # token layout in one collective
                out = jax.lax.psum_scatter(out, "model", scatter_dimension=0,
                                           tiled=True)
            else:
                out = jax.lax.psum(out, "model")
            aux = jax.lax.pmean(aux, "model")
        if tokens_sharded or tokens_full:
            aux = jax.lax.pmean(aux, dp_axes)
        return out, aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), spec_g, spec_g, spec_d, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    out, aux = fn(p["router"]["kernel"], p["w_gate"], p["w_up"], p["w_down"], x)
    if cfg.n_shared_experts > 0:
        out = out + glu_ffn(p["shared"], x)
    return out.astype(x.dtype), aux


def moe_dispatch(p: dict, cfg: MoEConfig, x: jax.Array,
                 inference: bool = False, lead: int | None = None):
    """Route to the shard_map expert-parallel path when a mesh is installed.

    ``lead``: leading batch dim of the caller's pre-flatten [B, S, d] (or
    [B, d]) activation — gates the full-mesh token sharding (see
    ``moe_apply_sharded``)."""
    from repro.dist.context import current_mesh, dp_axes
    mesh = current_mesh()
    if mesh is not None:
        return moe_apply_sharded(p, cfg, x, mesh, dp_axes(mesh),
                                 full_token_sharding=inference, lead=lead)
    return moe_apply(p, cfg, x)


def moe_apply(p: dict, cfg: MoEConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [T, d] -> (out [T, d], aux_loss scalar)."""
    from repro.dist.context import constrain
    from repro.dist.sharding import DP, EP

    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = moe_capacity(cfg, T)

    logits = (x.astype(jnp.float32) @ p["router"]["kernel"])          # [T, E]
    logits = constrain(logits, [[DP, "data"], None])
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(scores, K)                           # [T, K]
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # dense routing matrix R[t, e] = weight if e selected else 0
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)              # [T, K, E]
    R = jnp.einsum("tk,tke->te", top_w, onehot)
    R = constrain(R, [[DP, "data"], None])

    # per-expert top-C tokens by routing weight (capacity overflow drops
    # smallest); each expert-owning shard materializes only its expert rows
    RT = constrain(R.T, [[EP, "model", "data"], None])
    pr_vals, tok_idx = jax.lax.top_k(RT, min(C, T))                   # [E, C]
    keep = pr_vals > 0.0
    xe = jnp.take(x, tok_idx, axis=0)                                 # [E, C, d]
    xe = constrain(xe, [[EP, "model", "data"], None, None])
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = constrain(h, [[EP, "model", "data"], None, None])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])                   # [E, C, d]
    ye = constrain(ye, [[EP, "model", "data"], None, None])
    ye = ye * (pr_vals * keep.astype(pr_vals.dtype))[..., None].astype(ye.dtype)

    out = jnp.zeros((T, d), ye.dtype).at[tok_idx.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    out = constrain(out, [[DP, "data"], None])
    if cfg.n_shared_experts > 0:
        out = out + glu_ffn(p["shared"], x)

    # Switch-style load-balance auxiliary
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)           # [E]
    mean_prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)     # [E]
    aux = E * jnp.sum(frac_tokens * mean_prob)
    return out.astype(x.dtype), aux
