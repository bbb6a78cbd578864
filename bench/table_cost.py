"""Operations and bytes of training DLRM over row-sharded full tables,
from the configuration's shapes and the batches' ids, per chip of the
'model' axis.

``train_flops_per_example``: the model FLOPs one training example requires,
counted once however many chips repeat them: matrix products at 2 per
multiply-add, forward and backward (weights' and inputs' gradients each
cost the forward's again; the raw dense features need no gradient), the
lower triangle of the dot interaction.  Lookups, element-wise work, the
loss and the update are not counted.

``rows_per_chip``: of a batch's lookups, the ones each chip's slabs own,
and the distinct rows among them, summed over the tables.  A table of V
rows is padded to a multiple of the chip count and split evenly: chip r
owns rows [r * V' / n, (r + 1) * V' / n).

Bytes each chip requires, given those counts (``u``: its distinct rows,
``B``, ``F``, ``d``: batch, fields, width, 4-byte words):

- lookup, HBM: the ids (B F), the rows it owns once each (u d), and the
  [B, F, d] result every chip needs for its replicated MLPs (B F d);
- update, HBM: per distinct row its index, its gradient row read, its
  accumulator row and its table row read and written (1 + 5 d);
- exchange, sent over ICI: what the lookup's strategy sends (psum: an
  all-reduce of the [B, F, d] result, 2 (n - 1) / n of it; ring: n - 1
  hops of a chunk's ids and n of its partial sums, then an all-gather;
  all_to_all: an all-gather of the chunks' ids, an all-to-all of the
  partial sums, an all-gather of the result), and the update's (an
  all-reduce of the [B F, d] update values under psum, nothing under
  all_to_all, whose values stay owner-partial).
"""
from __future__ import annotations

import numpy as np

W = 4          # bytes of an f32 or int32 word


def _dense(dims: list[int], input_grad: bool = True) -> tuple[int, int]:
    """(forward, backward) FLOPs of an MLP over ``dims``."""
    fwd = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    bwd = 2 * fwd if input_grad else 2 * fwd - 2 * dims[0] * dims[1]
    return fwd, bwd


def train_flops_per_example(c: dict) -> int:
    F, d = len(c["vocab_sizes"]), c["d"]
    bf, bb = _dense([c["n_dense"], *c["bot_mlp"]], input_grad=False)
    n = F + 1
    pairs = n * (n - 1) // 2
    inter = 2 * pairs * d
    tf, tb = _dense([pairs + c["bot_mlp"][-1], *c["top_mlp"]])
    return bf + bb + inter + 2 * inter + tf + tb


def rows_per_chip(c: dict, sparse: np.ndarray, n: int) -> dict:
    """{"lookups": [n], "distinct": [n]} of one batch's ids [B, F]."""
    look, dist = np.zeros(n, np.int64), np.zeros(n, np.int64)
    for t, v in enumerate(c["vocab_sizes"]):
        slab = -(-v // n)
        ids = sparse[:, t].astype(np.int64)
        look += np.bincount(ids // slab, minlength=n)
        dist += np.bincount(np.unique(ids) // slab, minlength=n)
    return {"lookups": look.tolist(), "distinct": dist.tolist()}


def lookup_bytes(c: dict, batch: int, distinct: int) -> int:
    F, d = len(c["vocab_sizes"]), c["d"]
    return W * (batch * F + distinct * d + batch * F * d)


def update_bytes(c: dict, distinct: int) -> int:
    return W * distinct * (1 + 5 * c["d"])


def exchange_bytes(c: dict, batch: int, n: int, lookup: str,
                   update: str) -> float:
    """Bytes one chip sends for one step's table lookup (strategy
    ``lookup``) and table update (strategy ``update``)."""
    F, d = len(c["vocab_sizes"]), c["d"]
    out = W * batch * F * d
    ids = W * (batch // n) * F
    frac = (n - 1) / n
    sent = {"psum": 2 * frac * out,
            "ring": (n - 1) * ids + out + frac * out,
            "all_to_all": (n - 1) * ids + 2 * frac * out}[lookup]
    return sent + (2 * frac * out if update == "psum" else 0.0)
