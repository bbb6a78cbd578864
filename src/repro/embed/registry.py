"""Scheme protocol + decorator registry.

A *scheme* is the allocation policy of the paper (Definitions 1-2 generalized):
it decides how a value id maps onto trainable parameters.  Registering a new
one is a single decorated class in its own module — no edits to the dispatch
code in ``repro.embed.table`` or the backend resolver in
``repro.embed.backends`` (``repro/embed/freq.py`` is the in-repo proof).

Two families:

``memory``
    One shared pool ``params["memory"]`` ([m] floats) over the *global* value-id
    space; the scheme contributes a ``locations`` function ([N] gids ->
    [N, d] slots) and, optionally, a :class:`FusedSpec` so the fused Pallas
    engine can compute locations in-VMEM.  Lookups route through the backend
    resolver (split / fused / sharded).

``table``
    Per-table parameters (full, qr, md); the scheme embeds directly via
    ``embed_rows`` and no lookup backend is involved.  A scheme whose rows
    are the embeddings (``table_rows``, full) also has a sharded path.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, ClassVar

import jax

if TYPE_CHECKING:  # avoid a runtime cycle: config imports get_scheme lazily
    from repro.embed.config import EmbeddingConfig

_SCHEMES: dict[str, "Scheme"] = {}
_BUILTIN_LOADED = False


class Scheme:
    """Base class for embedding schemes; subclass + ``@register_scheme``.

    Required overrides: ``param_count``, ``init_params``, and — per family —
    ``locations`` (memory) or ``embed_rows`` (table).  Everything else has a
    sensible default.
    """

    kind: ClassVar[str]
    family: ClassVar[str] = "memory"       # "memory" | "table"
    needs_budget: ClassVar[bool] = True
    # True when ``locations`` are d-aligned pool rows (sparse_row_ids works):
    # the sparse-gradient pipeline then carries one index per row, and the
    # exchange cost model (repro.dist.exchange.sparse_worthwhile) prices the
    # d-times-smaller index vector and dedup sort.
    row_aligned: ClassVar[bool] = False
    # True when ``table_{t}`` rows are the embeddings themselves
    # (``embed_rows`` is a plain row gather, the full baseline):
    # ``embed_fields`` then records each table's rows for the sparse-gradient
    # engine, and under a mesh serves them from 'model'-sharded tables
    # (``sharded_lookup``).
    table_rows: ClassVar[bool] = False
    # What make_buffers consumes: None (no buffers), "signatures" (a
    # SignatureStore D', lma), or "id_counts" (per-global-id observed
    # counts, freq).  Launchers key data preparation on this.
    buffer_source: ClassVar[str | None] = None

    @property
    def needs_signature_store(self) -> bool:
        return self.buffer_source == "signatures"

    # ------------------------------------------------------ config surface
    def validate(self, cfg: "EmbeddingConfig") -> None:
        if self.needs_budget:
            assert cfg.budget is not None, f"{self.kind} needs a budget"

    def build_config(self, vocab_sizes: tuple[int, ...], dim: int,
                     budget: int | None, **kw) -> "EmbeddingConfig":
        """Default config for this scheme at a given scalar budget (used by
        ``configs._recsys_common.embedding_of_kind`` and the bench sweep).

        Foreign hyper-kwargs (another scheme's knobs, e.g. lma's ``n_h``
        reaching a hashed scheme through a kind-sweep) are dropped, so one
        sweep loop can pass a uniform kwarg set to every registered kind.
        """
        import dataclasses
        from repro.embed.config import EmbeddingConfig
        fields = {f.name for f in dataclasses.fields(EmbeddingConfig)}
        kw = {k: v for k, v in kw.items() if k in fields}
        return EmbeddingConfig(kind=self.kind, vocab_sizes=tuple(vocab_sizes),
                               dim=dim, budget=budget, **kw)

    def param_count(self, cfg: "EmbeddingConfig") -> int:
        raise NotImplementedError(self.kind)

    def describe(self, cfg: "EmbeddingConfig") -> dict:
        """JSON-serializable introspection row (dryrun/bench tables)."""
        d = {
            "kind": self.kind,
            "family": self.family,
            "n_tables": cfg.n_tables,
            "total_vocab": cfg.total_vocab,
            "dim": cfg.dim,
            "budget": cfg.budget,
            "param_count": self.param_count(cfg),
            "expansion_rate": round(cfg.expansion_rate, 4),
        }
        d.update(self.extra_describe(cfg))
        return d

    def extra_describe(self, cfg: "EmbeddingConfig") -> dict:
        return {}

    # ------------------------------------------------------- param surface
    def init_params(self, key: jax.Array, cfg: "EmbeddingConfig") -> dict:
        raise NotImplementedError(self.kind)

    def make_buffers(self, cfg: "EmbeddingConfig", store=None) -> dict:
        return {}

    def buffer_specs(self, cfg: "EmbeddingConfig",
                     n_store_rows: int) -> dict:
        """Abstract buffer layout: name -> (shape tuple, dtype str), for
        spec-only builders (dryrun bundles).  ``n_store_rows`` is the
        launcher's padded row count for row-sharded stores; schemes without
        buffers return {}."""
        return {}

    # ------------------------------------------- memory-family lookup hooks
    def locations(self, cfg: "EmbeddingConfig", buffers: dict,
                  gids: jax.Array) -> jax.Array:
        """[N] global ids -> [N, d] int32 slots into params['memory']."""
        raise NotImplementedError(self.kind)

    def memory_slots(self, cfg: "EmbeddingConfig") -> int:
        """The pool size the locations index modulo (fused-dispatch guard)."""
        return int(cfg.budget)

    def fused_spec(self, cfg: "EmbeddingConfig"):
        """FusedSpec for the Pallas engine, or None (-> split/sharded only)."""
        return None

    def fused_inputs(self, cfg: "EmbeddingConfig", buffers: dict,
                     gids: jax.Array) -> tuple:
        """Extra per-batch kernel inputs ((sets, support) for lma; () else)."""
        return ()

    def sharded_lookup(self, cfg: "EmbeddingConfig", params: dict,
                       buffers: dict, gids: jax.Array, mesh, dp_axes,
                       exchange=None):
        """Scheme-specific sharded path, or NotImplemented for the generic
        location-based lookup (dist.sharded_memory).  ``exchange`` is the
        cross-device strategy (psum | ring | all_to_all — a name, an
        :class:`repro.dist.exchange.Exchange`, or None for the
        ``resolve_exchange`` cost model), threaded through by
        ``repro.embed.backends.ShardedBackend``."""
        return NotImplemented

    def exchange_set_width(self, cfg: "EmbeddingConfig") -> int:
        """Signature-set row width this scheme's location math must exchange
        per batch row (lma's D' reconstruction), 0 for pure-hash schemes —
        the ``set_width`` input of the exchange cost model
        (``repro.dist.exchange.alloc_bytes_per_row``)."""
        return 0

    def sparse_buckets(self, cfg: "EmbeddingConfig") -> int:
        """Number of location buckets (= d) when this scheme's ``locations``
        satisfy the striped invariant — column j of the [N, d] tensor lies
        in ``[j*(m//d), (j+1)*(m//d))`` — else 0.

        A non-zero return lets the sparse-gradient engine build the pool's
        SparseGrad with d independent per-stripe sorts
        (``optim.sparse.from_bucketed_locations``) plus the sparse-update
        kernel's in-kernel duplicate fold, instead of one global
        O(K log K) argsort + segment-sum dedup."""
        return 0

    def sparse_row_ids(self, cfg: "EmbeddingConfig", buffers: dict,
                       gids: jax.Array):
        """[N] pool row ids when this scheme's locations are d-aligned rows
        (``locations == rows[:, None] * dim + arange(dim)``), else None.

        Row-aligned schemes (hashed_row, freq) let the sparse-gradient
        pipeline carry one index per row instead of d element locations —
        d-times smaller index traffic and a contiguous-row scatter, the
        layout production DLRM sparse optimizers (row-wise Adagrad/Adam)
        assume.  Semantics are unchanged: Adagrad/SGD moments stay
        elementwise within the row."""
        return None

    # -------------------------------------------- table-family embed hook
    def embed_rows(self, cfg: "EmbeddingConfig", params: dict, table: int,
                   flat_ids: jax.Array) -> jax.Array:
        """[N] table-local ids -> [N, dim] embeddings."""
        raise NotImplementedError(self.kind)


def register_scheme(cls: type) -> type:
    """Class decorator: instantiate and register under ``cls.kind``."""
    kind = getattr(cls, "kind", None)
    if not isinstance(kind, str) or not kind:
        raise TypeError(f"{cls.__name__} must define a string `kind`")
    _SCHEMES[kind] = cls()
    return cls


def _ensure_builtin() -> None:
    global _BUILTIN_LOADED
    if _BUILTIN_LOADED:
        return
    _BUILTIN_LOADED = True
    # import side-effect registration (mirrors configs.base._ensure_loaded)
    from repro.embed import freq, schemes  # noqa: F401


def get_scheme(kind: str) -> Scheme:
    _ensure_builtin()
    if kind not in _SCHEMES:
        raise KeyError(f"unknown embedding scheme {kind!r}; "
                       f"registered: {sorted(_SCHEMES)}")
    return _SCHEMES[kind]


def list_schemes() -> list[str]:
    _ensure_builtin()
    return sorted(_SCHEMES)
