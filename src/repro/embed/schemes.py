"""The six built-in schemes: full | hashed_elem | hashed_row | qr | lma | md.

Param pytree key names are a checkpoint-compatibility contract and must not
change: ``table_{t}`` (full, md), ``memory`` (hashed_*, lma), ``q_{t}``/
``r_{t}`` (qr), ``proj_{t}`` (md).  Buffer keys likewise (``store_*``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import allocation as alc
from repro.core.allocation import LMAParams
from repro.core.memory import init_memory
from repro.core.minhash import gather_ragged_sets
from repro.core.signatures import DenseSignatureStore, SignatureStore
from repro.embed.config import EmbeddingConfig
from repro.embed.registry import Scheme, register_scheme


# --------------------------------------------------------------------- full

@register_scheme
class FullScheme(Scheme):
    """One uncompressed [V, d] table per field (the paper's A_full baseline)."""

    kind = "full"
    family = "table"
    needs_budget = False
    table_rows = True

    def build_config(self, vocab_sizes, dim, budget, **kw):
        kw.pop("budget", None)
        return super().build_config(vocab_sizes, dim, None, **kw)

    def param_count(self, cfg):
        return cfg.total_vocab * cfg.dim

    def init_params(self, key, cfg):
        scale = cfg.scale_or_default()
        keys = jax.random.split(key, cfg.n_tables)
        return {
            f"table_{t}": (jax.random.normal(keys[t], (v, cfg.dim)) * scale
                           ).astype(cfg.jdtype)
            for t, v in enumerate(cfg.vocab_sizes)
        }

    def embed_rows(self, cfg, params, table, flat_ids):
        return jnp.take(params[f"table_{table}"], flat_ids.astype(jnp.int32),
                        axis=0)

    def sharded_lookup(self, cfg, params, buffers, ids, mesh, dp_axes,
                       exchange=None):
        """ids [B, F] (field-local) -> [B, F, d] from the tables row-sharded
        over 'model' (lane-packed, ``repro.dist.sharding.row_table_shape``):
        one shard_map serves every field through an exchange strategy
        (``repro.dist.sharded_memory.sharded_table_lookup``)."""
        from repro.dist.sharded_memory import sharded_table_lookup
        return sharded_table_lookup(
            [params[f"table_{t}"] for t in range(cfg.n_tables)], ids,
            cfg.dim, mesh, dp_axes, exchange=exchange)


# ------------------------------------------------------------------- hashed

class _HashedBase(Scheme):
    """Common memory + pure-hash locations (HashedNet-style tricks)."""

    def init_params(self, key, cfg):
        self.validate(cfg)
        return {"memory": init_memory(key, cfg.budget, "normal",
                                      cfg.scale_or_default(), cfg.jdtype)}

    def param_count(self, cfg):
        assert cfg.budget is not None
        return int(cfg.budget)

    def fused_spec(self, cfg):
        from repro.kernels.fused_embed import ops as fe
        return fe.hashed_spec(self.kind, cfg.dim, cfg.budget, cfg.seed)

    def sharded_lookup(self, cfg, params, buffers, gids, mesh, dp_axes,
                       exchange=None):
        from repro.dist.sharded_memory import sharded_hashed_lookup
        return sharded_hashed_lookup(params["memory"], gids, cfg.dim,
                                     cfg.budget, cfg.seed, mesh, dp_axes,
                                     kind=self.kind, exchange=exchange)


@register_scheme
class HashedElemScheme(_HashedBase):
    kind = "hashed_elem"

    def locations(self, cfg, buffers, gids):
        return alc.alloc_hashed_elem(gids, cfg.dim, cfg.budget, cfg.seed)


@register_scheme
class HashedRowScheme(_HashedBase):
    kind = "hashed_row"
    row_aligned = True

    def locations(self, cfg, buffers, gids):
        return alc.alloc_hashed_row(gids, cfg.dim, cfg.budget, cfg.seed)

    def sparse_row_ids(self, cfg, buffers, gids):
        # the row index of alloc_hashed_row, bit-for-bit
        from repro.core.hashing import hash_u32, seed_stream
        n_rows = max(cfg.budget // cfg.dim, 1)
        seeds = seed_stream(cfg.seed, 1)
        row = hash_u32(gids.astype(jnp.uint32), seeds[0]) \
            % jnp.uint32(n_rows)
        return row.astype(jnp.int32)


# ---------------------------------------------------------------------- lma

@register_scheme
class LMAScheme(Scheme):
    """The paper's semantically-constrained allocation A_L (section 4)."""

    kind = "lma"
    buffer_source = "signatures"

    def validate(self, cfg):
        super().validate(cfg)
        assert cfg.lma is not None, "lma needs LMAParams"

    def build_config(self, vocab_sizes, dim, budget, n_h: int = 4,
                     max_set: int = 32, seed: int = 0,
                     striped: bool | None = None, **kw):
        kw.setdefault("memory_init", "bernoulli")
        # training configs pin the 1/sqrt(d) activation scale explicitly;
        # with init_scale=None the scheme keeps Theorem 2's unit +/-1 entries
        # (cosine concentration is scale-invariant, conditioning is not)
        kw.setdefault("init_scale", 1.0 / np.sqrt(dim))
        # production configs default to the striped location layout: the
        # sparse-update dedup then runs bucketed (from_bucketed_locations +
        # in-kernel fold) instead of a global argsort, for a collision-floor
        # cost of 1/m -> d/m (negligible at production budgets).  Ragged
        # budgets keep the flag inert (LMAParams.stripe == 0).
        if striped is None:
            striped = budget is not None and budget % dim == 0
        return EmbeddingConfig(
            kind="lma", vocab_sizes=tuple(vocab_sizes), dim=dim, budget=budget,
            lma=LMAParams(d=dim, m=budget, n_h=n_h, max_set=max_set,
                          seed=seed, striped=striped),
            seed=seed, **kw)

    def param_count(self, cfg):
        assert cfg.budget is not None
        return int(cfg.budget)

    def init_params(self, key, cfg):
        self.validate(cfg)
        scale = cfg.init_scale
        if scale is None:
            # Theorem 2's Bernoulli init keeps the unit +/-1 scale (cosine
            # concentration needs the raw sign pattern); only the scaled
            # normal init takes the 1/sqrt(d) activation-variance factor.
            scale = 1.0 if cfg.memory_init == "bernoulli" \
                else 1.0 / np.sqrt(cfg.dim)
        return {"memory": init_memory(key, cfg.budget, cfg.memory_init, scale,
                                      cfg.jdtype)}

    def buffer_specs(self, cfg, n_store_rows):
        return {"store_sets": ((n_store_rows, cfg.lma.max_set), "uint32"),
                "store_lengths": ((n_store_rows,), "int32")}

    def make_buffers(self, cfg, store=None):
        assert store is not None, "LMA needs a SignatureStore (D')"
        if isinstance(store, DenseSignatureStore):
            return {"store_sets": store.sets, "store_lengths": store.lengths}
        return {"store_flat": store.flat, "store_offsets": store.offsets,
                "store_lengths": store.lengths}

    @staticmethod
    def store_from_buffers(buffers: dict):
        if "store_sets" in buffers:
            return DenseSignatureStore(buffers["store_sets"],
                                       buffers["store_lengths"])
        return SignatureStore(buffers["store_flat"], buffers["store_offsets"],
                              buffers["store_lengths"])

    def locations(self, cfg, buffers, gids):
        return alc.alloc_lma(cfg.lma, self.store_from_buffers(buffers), gids)

    def memory_slots(self, cfg):
        return int(cfg.lma.m)

    def fused_spec(self, cfg):
        from repro.kernels.fused_embed import ops as fe
        return fe.lma_spec(cfg.lma)

    def fused_inputs(self, cfg, buffers, gids):
        """D' rows + support for a flat [N] gid batch, in the PAD-sentinel
        form the kernel masks on — bit-identical inputs to ``alloc_lma``'s."""
        p = cfg.lma
        if "store_sets" in buffers:
            rows = jnp.take(buffers["store_sets"], gids, axis=0)[:, : p.max_set]
        else:
            elems, mask = gather_ragged_sets(buffers["store_flat"],
                                             buffers["store_offsets"], gids,
                                             p.max_set)
            rows = jnp.where(mask, elems, DenseSignatureStore.PAD)
        support = jnp.take(buffers["store_lengths"], gids, axis=0)
        return rows, support

    def sharded_lookup(self, cfg, params, buffers, gids, mesh, dp_axes,
                       exchange=None):
        from repro.dist.sharded_memory import (sharded_lma_lookup,
                                               sharded_lma_lookup_csr)
        if "store_flat_sh" in buffers:
            # 'model'-sharded CSR store (shard_csr_buffers): ragged sets
            # reconstructed through Exchange.partial_sum_lookup — the store
            # no longer replicates
            return sharded_lma_lookup_csr(
                params["memory"], buffers["store_flat_sh"],
                buffers["store_offsets_sh"], buffers["store_lengths"], gids,
                cfg.lma, mesh, dp_axes, exchange=exchange)
        if "store_sets" in buffers:
            return sharded_lma_lookup(params["memory"], buffers["store_sets"],
                                      buffers["store_lengths"], gids, cfg.lma,
                                      mesh, dp_axes, exchange=exchange)
        # raw (unsharded) CSR buffers: generic location fallback — the
        # store stays replicated; run shard_csr_buffers at setup to shard it
        return NotImplemented

    def exchange_set_width(self, cfg):
        return int(cfg.lma.max_set)

    def sparse_buckets(self, cfg):
        return cfg.lma.d if cfg.lma.stripe else 0

    def extra_describe(self, cfg):
        p = cfg.lma
        return {"n_h": p.n_h, "max_set": p.max_set,
                "min_support": p.min_support, "striped": p.striped,
                "memory_init": cfg.memory_init}


# ----------------------------------------------------------------------- qr

def _qr_rows_budget(vocab: int, dim: int, budget: int, total_vocab: int) -> int:
    """Row budget for one table: its proportional share of the scalar budget."""
    share = max(budget * (vocab / max(total_vocab, 1)), 4 * dim)
    return max(int(share // dim), 4)


def _qr_rows(vocab: int, dim: int, budget: int, total_vocab: int) -> tuple[int, int]:
    """(quotient rows mq, remainder rows mr) with mq + mr <= rows_budget.

    mq ~= sqrt(vocab) minimizes collisions; mr = ceil(vocab / mq) when the
    budget allows (then ``(v // mq) % mr == v // mq`` — collision-free in the
    quotient, identical to the unconstrained QR trick), else mr is clamped to
    the remaining row budget and the quotient index wraps (hash-style
    collisions instead of a blown budget)."""
    rows_budget = _qr_rows_budget(vocab, dim, budget, total_vocab)
    mq = int(np.sqrt(max(vocab, 1)))
    mq = max(2, min(mq, rows_budget - 2))
    mr = max(2, min(-(-vocab // mq), rows_budget - mq))
    return mq, mr


@register_scheme
class QRScheme(Scheme):
    """Quotient-remainder trick: element-wise product of two small tables."""

    kind = "qr"
    family = "table"

    def param_count(self, cfg):
        assert cfg.budget is not None
        n = 0
        for v in cfg.vocab_sizes:
            mq, mr = _qr_rows(v, cfg.dim, cfg.budget, cfg.total_vocab)
            assert mq + mr <= _qr_rows_budget(v, cfg.dim, cfg.budget,
                                              cfg.total_vocab), \
                (v, mq, mr, "qr tables exceed this table's budget share")
            n += (mq + mr) * cfg.dim
        return n

    def init_params(self, key, cfg):
        self.validate(cfg)
        scale = cfg.scale_or_default()
        params = {}
        keys = jax.random.split(key, 2 * cfg.n_tables)
        for t, v in enumerate(cfg.vocab_sizes):
            mq, mr = _qr_rows(v, cfg.dim, cfg.budget, cfg.total_vocab)
            params[f"q_{t}"] = (jax.random.normal(keys[2 * t], (mq, cfg.dim))
                                * scale).astype(cfg.jdtype)
            # remainder table multiplies element-wise; init around 1 so the
            # product starts near the quotient embedding
            params[f"r_{t}"] = (1.0 + jax.random.normal(keys[2 * t + 1],
                                                        (mr, cfg.dim))
                                * scale).astype(cfg.jdtype)
        return params

    def embed_rows(self, cfg, params, table, flat_ids):
        v = flat_ids.astype(jnp.int32)
        mq = params[f"q_{table}"].shape[0]
        mr = params[f"r_{table}"].shape[0]
        eq = jnp.take(params[f"q_{table}"], v % mq, axis=0)
        # % mr is the identity when the budget admitted mr == ceil(v / mq)
        er = jnp.take(params[f"r_{table}"], (v // mq) % mr, axis=0)
        return eq * er


# ----------------------------------------------------------------------- md

@register_scheme
class MDScheme(Scheme):
    """Mixed-dimension tables: narrow per-table embeddings + up-projection."""

    kind = "md"
    family = "table"
    needs_budget = False

    def validate(self, cfg):
        assert cfg.md_dims is not None, "md needs md_dims"
        assert len(cfg.md_dims) == cfg.n_tables, (cfg.md_dims, cfg.n_tables)

    def build_config(self, vocab_sizes, dim, budget, **kw):
        if "md_dims" not in kw and budget is not None:
            kw["md_dims"] = self._dims_for_budget(tuple(vocab_sizes), dim,
                                                  budget)
        return super().build_config(vocab_sizes, dim, budget, **kw)

    @staticmethod
    def _dims_for_budget(vocab_sizes, dim, budget) -> tuple[int, ...]:
        """Per-table dims ~ proportional to each table's budget share,
        clamped to [1, dim] (mixed-dimension heuristic)."""
        total = max(sum(vocab_sizes), 1)
        dims = []
        for v in vocab_sizes:
            share = budget * (v / total)
            dims.append(int(max(1, min(dim, share // max(v + dim, 1)))))
        return tuple(dims)

    def param_count(self, cfg):
        self.validate(cfg)
        return int(sum(v * d + d * cfg.dim
                       for v, d in zip(cfg.vocab_sizes, cfg.md_dims)))

    def init_params(self, key, cfg):
        self.validate(cfg)
        params = {}
        keys = jax.random.split(key, 2 * cfg.n_tables)
        for t, (v, dt_dim) in enumerate(zip(cfg.vocab_sizes, cfg.md_dims)):
            scale = cfg.scale_or_default(dt_dim)
            params[f"table_{t}"] = (jax.random.normal(keys[2 * t], (v, dt_dim))
                                    * scale).astype(cfg.jdtype)
            params[f"proj_{t}"] = (jax.random.normal(keys[2 * t + 1],
                                                     (dt_dim, cfg.dim))
                                   / np.sqrt(dt_dim)).astype(cfg.jdtype)
        return params

    def embed_rows(self, cfg, params, table, flat_ids):
        e = jnp.take(params[f"table_{table}"], flat_ids.astype(jnp.int32),
                     axis=0)
        return e @ params[f"proj_{table}"]

    def extra_describe(self, cfg):
        return {"md_dims": list(cfg.md_dims)}
