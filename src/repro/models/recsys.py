"""RecSys / CTR model zoo: DLRM, DCN-v2, xDeepFM, DIN.

Every model draws categorical embeddings through one ``repro.embed``
:class:`EmbeddingTable` — the paper's LMA (and every registered baseline:
full / hashed / QR / MD / freq / ...) is a config switch on
``EmbeddingConfig.kind``, with one common memory across all fields ("Common
Memory", paper section 5).

Batch format (dict of arrays):
  dense      [B, n_dense]  float   (DLRM/DCN: 13 ints log-transformed upstream)
  sparse     [B, n_fields] int32   (field-local ids)
  hist       [B, L]        int32   (DIN behaviour sequence, item ids)
  hist_mask  [B, L]        bool
  target     [B]           int32   (DIN candidate item)
  label      [B]           float32

Serving:
  ``forward``     -> logits [B] (online/bulk scoring; same graph, bigger batch)
  ``retrieval``   -> scores [n_candidates] for one context, scanned in chunks so
                     the 1M-candidate cell never materializes [C, ...] MLP blocks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.embed import EmbeddingConfig, EmbeddingTable
from repro.nn.modules import dense, dense_init, mlp, mlp_init


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    model: str                     # dlrm | dcn | xdeepfm | din
    embedding: EmbeddingConfig
    n_dense: int = 0
    # dlrm
    bot_mlp: tuple[int, ...] = ()
    top_mlp: tuple[int, ...] = ()
    # dcn
    n_cross_layers: int = 0
    deep_mlp: tuple[int, ...] = ()
    # xdeepfm
    cin_layers: tuple[int, ...] = ()
    # din
    hist_len: int = 0
    attn_mlp: tuple[int, ...] = ()
    dtype: str = "float32"

    @property
    def n_fields(self) -> int:
        return self.embedding.n_tables

    @property
    def table(self) -> EmbeddingTable:
        return EmbeddingTable(self.embedding)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


def lookups_per_example(cfg: RecsysConfig) -> int:
    """Embedding-row lookups one example performs — the one definition the
    trainer's lookups_per_sec stat and the launch-time sparse-vs-dense
    traffic model (steps._sparse_worthwhile) both use."""
    return (cfg.hist_len + 1) if cfg.model == "din" else cfg.n_fields


# ------------------------------------------------------------------ components

def dot_interaction(feats: jax.Array, self_interaction: bool = False) -> jax.Array:
    """DLRM pairwise dot: feats [B, F, d] -> [B, F*(F-1)/2] (lower triangle)."""
    B, F, d = feats.shape
    z = jnp.einsum("bfd,bgd->bfg", feats, feats)
    ii, jj = np.tril_indices(F, k=0 if self_interaction else -1)
    return z[:, ii, jj]


def cross_layer(p: dict, x0: jax.Array, x: jax.Array) -> jax.Array:
    """DCN-v2 full-rank cross: x0 * (W x + b) + x."""
    return x0 * dense(p, x) + x


def cin_layer(w: jax.Array, xk: jax.Array, x0: jax.Array) -> jax.Array:
    """xDeepFM CIN: xk [B, Hk, d], x0 [B, F, d], w [Ho, Hk, F] -> [B, Ho, d]."""
    z = jnp.einsum("bhd,bfd->bhfd", xk, x0)
    return jnp.einsum("bhfd,ohf->bod", z, w)


# ------------------------------------------------------------------------ init

def init(key, cfg: RecsysConfig) -> dict:
    keys = jax.random.split(key, 8)
    d = cfg.embedding.dim
    F = cfg.n_fields
    params: dict = {"embedding": cfg.table.init(keys[0])}
    if cfg.model == "dlrm":
        params["bot"] = mlp_init(keys[1], [cfg.n_dense, *cfg.bot_mlp])
        n_feats = F + 1                      # fields + bottom-mlp output
        d_inter = n_feats * (n_feats - 1) // 2 + cfg.bot_mlp[-1]
        params["top"] = mlp_init(keys[2], [d_inter, *cfg.top_mlp])
    elif cfg.model == "dcn":
        d_x0 = F * d + cfg.n_dense
        params["cross"] = {
            f"layer_{i}": dense_init(jax.random.fold_in(keys[1], i), d_x0, d_x0)
            for i in range(cfg.n_cross_layers)}
        params["deep"] = mlp_init(keys[2], [d_x0, *cfg.deep_mlp])
        params["head"] = dense_init(keys[3], d_x0 + cfg.deep_mlp[-1], 1)
    elif cfg.model == "xdeepfm":
        hk = F
        params["cin"] = {}
        for i, ho in enumerate(cfg.cin_layers):
            s = 1.0 / np.sqrt(hk * F)
            params["cin"][f"layer_{i}"] = (
                jax.random.normal(jax.random.fold_in(keys[1], i), (ho, hk, F)) * s
            ).astype(cfg.jdtype)
            hk = ho
        params["cin_out"] = dense_init(keys[2], sum(cfg.cin_layers), 1)
        params["deep"] = mlp_init(keys[3], [F * d, *cfg.deep_mlp, 1])
        # first-order (wide) term: dim-1 embedding per field, common memory too
        params["linear"] = EmbeddingTable(_linear_cfg(cfg)).init(keys[4])
    elif cfg.model == "din":
        att_in = 4 * d
        params["att"] = mlp_init(keys[1], [att_in, *cfg.attn_mlp, 1])
        params["head"] = mlp_init(keys[2], [3 * d + cfg.n_dense,
                                            *cfg.top_mlp, 1])
    else:
        raise ValueError(cfg.model)
    return params


def _linear_cfg(cfg: RecsysConfig) -> EmbeddingConfig:
    d = cfg.embedding.dim
    if cfg.embedding.kind == "full":
        return dataclasses.replace(cfg.embedding, dim=1, budget=None, lma=None)
    # keep the derived budget divisible by every mesh axis combination
    # (the sharded lookup shard_maps the memory over the model axis)
    m_lin = max(cfg.embedding.budget // max(d, 1), 4096)
    m_lin = -(-m_lin // 4096) * 4096
    return dataclasses.replace(
        cfg.embedding, dim=1, budget=m_lin,
        lma=None if cfg.embedding.lma is None else
        dataclasses.replace(cfg.embedding.lma, d=1, m=m_lin))


# --------------------------------------------------------------------- forward

def forward(params: dict, cfg: RecsysConfig, batch: dict,
            buffers: dict | None = None) -> jax.Array:
    """-> logits [B]."""
    buffers = buffers or {}
    if cfg.model == "din":
        return _din_forward(params, cfg, batch, buffers)
    feats = cfg.table.embed_fields(params["embedding"], buffers,
                                   batch["sparse"])              # [B,F,d]
    with jax.named_scope("dense_net"):
        return _field_forward(params, cfg, batch, buffers, feats)


def _field_forward(params, cfg, batch, buffers, feats):
    """Everything of a field model after its embedding lookup -> logits."""
    B = feats.shape[0]
    if cfg.model == "dlrm":
        bot = mlp(params["bot"], batch["dense"].astype(cfg.jdtype), act=jax.nn.relu,
                  final_act=jax.nn.relu)                                    # [B, d]
        allf = jnp.concatenate([bot[:, None, :], feats], axis=1)
        z = dot_interaction(allf)
        top_in = jnp.concatenate([bot, z], axis=-1)
        return mlp(params["top"], top_in)[:, 0]
    if cfg.model == "dcn":
        x0 = jnp.concatenate([feats.reshape(B, -1),
                              batch["dense"].astype(cfg.jdtype)], axis=-1)
        x = x0
        for i in range(cfg.n_cross_layers):
            x = cross_layer(params["cross"][f"layer_{i}"], x0, x)
        deep = mlp(params["deep"], x0, act=jax.nn.relu, final_act=jax.nn.relu)
        return dense(params["head"], jnp.concatenate([x, deep], -1))[:, 0]
    if cfg.model == "xdeepfm":
        x0 = feats
        xk = x0
        pools = []
        for i, _ho in enumerate(cfg.cin_layers):
            xk = jax.nn.relu(cin_layer(params["cin"][f"layer_{i}"], xk, x0))
            pools.append(jnp.sum(xk, axis=-1))                              # [B, Ho]
        cin_logit = dense(params["cin_out"], jnp.concatenate(pools, -1))[:, 0]
        deep_logit = mlp(params["deep"], feats.reshape(B, -1))[:, 0]
        lin = EmbeddingTable(_linear_cfg(cfg)).embed_fields(
            params["linear"], buffers, batch["sparse"])                     # [B,F,1]
        lin_logit = jnp.sum(lin, axis=(1, 2))
        return cin_logit + deep_logit + lin_logit
    raise ValueError(cfg.model)


def _din_attention(params, cfg, e_hist, mask, e_t):
    """e_hist [B?, L, d], e_t [B?, d] -> pooled [B?, d] (no softmax, per paper)."""
    et_b = jnp.broadcast_to(e_t[..., None, :], e_hist.shape)
    att_in = jnp.concatenate(
        [e_hist, et_b, e_hist - et_b, e_hist * et_b], axis=-1)
    w = mlp(params["att"], att_in, act=jax.nn.sigmoid)[..., 0]     # [B?, L]
    w = jnp.where(mask, w, 0.0)
    return jnp.einsum("...l,...ld->...d", w, e_hist)


def _din_forward(params, cfg, batch, buffers):
    t = cfg.table
    e_hist = t.embed(params["embedding"], buffers, 0, batch["hist"])    # [B,L,d]
    e_t = t.embed(params["embedding"], buffers, 0, batch["target"])     # [B,d]
    with jax.named_scope("dense_net"):
        return _din_head(params, cfg, batch, e_hist, e_t)


def _din_head(params, cfg, batch, e_hist, e_t):
    pooled = _din_attention(params, cfg, e_hist, batch["hist_mask"], e_t)
    head_in = [pooled, e_t, pooled * e_t]
    if cfg.n_dense:
        head_in.append(batch["dense"].astype(cfg.jdtype))
    return mlp(params["head"], jnp.concatenate(head_in, -1))[:, 0]


def loss_fn(params: dict, cfg: RecsysConfig, batch: dict,
            buffers: dict | None = None):
    logits = forward(params, cfg, batch, buffers).astype(jnp.float32)
    with jax.named_scope("dense_net"):
        y = batch["label"].astype(jnp.float32)
        # numerically-stable BCE-with-logits
        ce = jnp.mean(jnp.maximum(logits, 0) - logits * y
                      + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return ce, {"ce": ce, "logits": logits}


# ------------------------------------------------------------------- retrieval

def retrieval(params: dict, cfg: RecsysConfig, batch: dict,
              candidates: jax.Array, buffers: dict | None = None,
              chunk: int = 8192) -> jax.Array:
    """Score one context against [C] candidate items, chunked over C.

    For DIN the candidate replaces ``target``; for field models it replaces the
    *first* sparse field (the item field by convention).
    """
    buffers = buffers or {}
    C = candidates.shape[0]
    nc = -(-C // chunk)
    cand = jnp.pad(candidates, (0, nc * chunk - C)).reshape(nc, chunk)

    def score_chunk(_, cand_c):
        b = dict(batch)
        if cfg.model == "din":
            rep = lambda a: jnp.broadcast_to(a, (chunk, *a.shape[1:]))
            b = {"hist": rep(batch["hist"]), "hist_mask": rep(batch["hist_mask"]),
                 "target": cand_c}
            if cfg.n_dense:
                b["dense"] = rep(batch["dense"])
        else:
            sparse = jnp.broadcast_to(batch["sparse"], (chunk, cfg.n_fields))
            sparse = sparse.at[:, 0].set(cand_c)
            b = {"sparse": sparse,
                 "dense": jnp.broadcast_to(batch["dense"],
                                           (chunk, cfg.n_dense))
                 if cfg.n_dense else batch.get("dense")}
        return None, forward(params, cfg, b, buffers)

    _, scores = jax.lax.scan(score_chunk, None, cand)
    return scores.reshape(-1)[:C]
