"""Operations and bytes the models require, from the configuration's shapes.

``train_flops_per_example``: the floating-point operations one training
example requires, forward and backward: matrix products at 2 per
multiply-add (weights' and inputs' gradients each cost the forward's
again; the raw dense features need no gradient), the CIN's outer products
at 1 per product forward and 4 backward (a multiply-add into each
factor's gradient).  Only the lower
triangle of DLRM's dot products is required.  Element-wise activations,
biases and the loss are not counted, nor is anything recomputed.

``lookup_bytes``: the HBM bytes one forward lookup of a batch requires:
the ids, each id's D' row (``max_set`` uint32) and set size, the pool
scalars it gathers and the embeddings it writes, for every LMA pool the
model reads.
"""
from __future__ import annotations


def _dense(dims: list[int], input_grad: bool = True) -> tuple[int, int]:
    """(forward, backward) FLOPs of an MLP over ``dims``."""
    fwd = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    bwd = 2 * fwd if input_grad else 2 * fwd - 2 * dims[0] * dims[1]
    return fwd, bwd


def train_flops_per_example(c: dict) -> int:
    F, d = len(c["vocab_sizes"]), c["lma"]["d"]
    if c["model"] == "dlrm":
        bf, bb = _dense([c["n_dense"], *c["bot_mlp"]], input_grad=False)
        n = F + 1
        pairs = n * (n - 1) // 2
        inter = 2 * pairs * d
        tf, tb = _dense([pairs + c["bot_mlp"][-1], *c["top_mlp"]])
        return bf + bb + inter + 2 * inter + tf + tb
    if c["model"] == "xdeepfm":
        total, hk = 0, F
        for ho in c["cin_layers"]:
            outer = hk * F * d
            contract = 2 * ho * hk * F * d
            total += outer + 4 * outer + contract + 2 * contract
            hk = ho
        of, ob = _dense([sum(c["cin_layers"]), 1])
        df, db = _dense([F * d, *c["deep_mlp"], 1])
        return total + of + ob + df + db
    raise ValueError(c["model"])


def lookup_bytes(c: dict, batch: int) -> int:
    n = batch * len(c["vocab_sizes"])
    pools = [c["lma"]] + ([c["linear_lma"]] if "linear_lma" in c else [])
    per_id = sum(4 + 4 * p["max_set"] + 4 + 4 * p["d"] + 4 * p["d"]
                 for p in pools)
    return n * per_id
