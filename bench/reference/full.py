"""Plain float32 reference of DLRM over full tables and its Adagrad training.

DLRM (arXiv:1906.00091) with one uncompressed [V_t, d] table per field (the
LMA paper's A_full baseline): each field's embedding is row ``id`` of its
table; the bottom MLP, the pairwise dot products and the top MLP are
``reference.recsys``'s.  Binary cross-entropy with logits, averaged over the
batch; Adagrad ``acc += g**2; p -= lr * g / (sqrt(acc) + eps)`` on every
parameter.

The tables hold 33.76M rows at Criteo width, more than one chip holds with
the rest, so the reference keeps only the rows the check batches touch:
each table is drawn whole from the seed on one device, one at a time, the
touched rows are gathered into a compact table and the table is freed.
Adagrad leaves an untouched row as it was, so the compact tables train
exactly as the full ones do, and the norms of their first gradients and of
their changes are the full tables' norms.  Draws follow ``bench.weights``
(``jax_threefry_partitionable``): a row's values depend on the seed, the
table's path and the row's index alone, so they equal the program's
sharded draws of its padded tables.

Parameters are a flat ``{path: array}`` dict whose paths and shapes
``param_spec`` derives from the configuration alone.
"""
from __future__ import annotations

import functools
import json

import numpy as np

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import recsys as ref


def param_spec(c: dict) -> dict:
    """{path: (shape, init, scale)} at the configuration's own shapes."""
    spec = {f"embedding/table_{t}": ((v, c["d"]), "normal", c["table_scale"])
            for t, v in enumerate(c["vocab_sizes"])}

    def mlp(name, dims):
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            spec[f"{name}/layer_{i}/kernel"] = ((a, b), "normal", a ** -0.5)
            spec[f"{name}/layer_{i}/bias"] = ((b,), "zeros", 0.0)

    n = len(c["vocab_sizes"]) + 1
    mlp("bot", [c["n_dense"], *c["bot_mlp"]])
    mlp("top", [n * (n - 1) // 2 + c["bot_mlp"][-1], *c["top_mlp"]])
    return dict(sorted(spec.items()))


def forward(c: dict, p: dict, batch: dict, rows, prec: str):
    """Logits [B]; ``rows`` [B, F]: each field's row in its compact table."""
    F = len(c["vocab_sizes"])
    feats = jnp.stack([p[f"embedding/table_{t}"][rows[:, t]]
                       for t in range(F)], axis=1)                 # [B, F, d]
    bot = ref._mlp(p, "bot", batch["dense"], prec, final_relu=True)
    allf = jnp.concatenate([bot[:, None, :], feats], axis=1)
    z = ref.matmul("bfd,bgd->bfg", allf, allf, prec)
    ii, jj = np.tril_indices(F + 1, k=-1)
    return ref._mlp(p, "top", jnp.concatenate([bot, z[:, ii, jj]], -1),
                    prec)[:, 0]


def loss_sum(c, p, batch, rows, prec, weight):
    """Sum over the block of ``weight`` * per-example cross-entropy."""
    x = forward(c, p, batch, rows, prec)
    y = batch["label"]
    ce = jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return jnp.sum(ce * weight)


@functools.lru_cache(maxsize=None)
def _programs(c_json: str, prec: str):
    """Jitted block gradient and Adagrad step of one configuration."""
    c = json.loads(c_json)
    o = c["optimizer"]

    def adagrad(p, acc, g):
        acc = {k: acc[k] + jnp.square(g[k]) for k in p}
        return {k: p[k] - o["lr"] * g[k] / (jnp.sqrt(acc[k]) + o["eps"])
                for k in p}, acc

    grad = jax.jit(lambda p, b, r, w: jax.value_and_grad(
        lambda q: loss_sum(c, q, b, r, prec, w))(p))
    return grad, jax.jit(adagrad)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _touched(key, path: str, shape: tuple, init: str, scale: float, idx):
    """Rows ``idx`` of the table the seed draws at ``path``."""
    return jnp.take(weights._draw(key, path, shape, init, scale), idx, axis=0)


def compact(c: dict, seed: int, batches: list) -> tuple[dict, np.ndarray]:
    """({table path: compact table}, [n_batches, B, F] compact rows).

    A table's compact form holds the rows the batches touch, in id order,
    padded to min(V, all lookups of the batches) rows with copies of its
    first touched row, which no id addresses: the shapes do not depend on
    the seed, so the reference compiles once."""
    sparse = np.stack([b["sparse"] for b in batches]).astype(np.int64)
    spec, key = param_spec(c), weights.base_key(seed)
    tables, rows = {}, np.empty_like(sparse, dtype=np.int32)
    for t, v in enumerate(c["vocab_sizes"]):
        path = f"embedding/table_{t}"
        shape, init, scale = spec[path]
        uniq = np.unique(sparse[..., t])
        n = min(v, sparse[..., t].size)
        idx = np.concatenate([uniq, np.full(n - uniq.size, uniq[0])])
        tables[path] = _touched(key, path, tuple(shape), init, float(scale),
                                jnp.asarray(idx, jnp.int32))
        rows[..., t] = np.searchsorted(uniq, sparse[..., t])
    return tables, rows


@functools.partial(jax.jit, static_argnums=(1,))
def _filled(p: dict, value: float) -> dict:
    return {k: jnp.full_like(v, value) for k, v in p.items()}


@jax.jit
def _add(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] for k in a}


@jax.jit
def _norms(a: dict, b: dict | None = None) -> dict:
    """{path: ||a - b||} (``b`` None: ||a||), in one call."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        v if b is None else v - b[k]))) for k, v in a.items()}


def _floats(tree: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def grads(c: dict, p: dict, batch: dict, rows: np.ndarray, prec: str,
          half: bool = False, block: int = 0):
    """(mean loss, {path: gradient}) over the batch, ``block`` rows at a
    time.  ``half``: the mean is taken over the first half alone (the
    planted fault of a step that leaves half of its batch out)."""
    B = batch["label"].shape[0]
    n = B // 2 if half else B
    weight = np.where(np.arange(B) < n, 1.0 / n, 0.0).astype(np.float32)
    grad = _programs(ref._key(c), prec)[0]
    block = block or c["reference_block_rows"]
    loss, total = 0.0, None
    for lo in range(0, B, block):
        sl = slice(lo, min(lo + block, B))
        blk = {k: jnp.asarray(v[sl]) for k, v in batch.items()}
        lv, g = grad(p, blk, jnp.asarray(rows[sl]), jnp.asarray(weight[sl]))
        loss += float(lv)
        total = g if total is None else _add(total, g)
    return loss, total


def train(c: dict, seed: int, batches: list, prec: str = "highest",
          half: bool = False):
    """Adagrad over ``batches`` from the seed's weights -> (losses, each
    path's first gradient norm, each path's change norm)."""
    spec = param_spec(c)
    tables, rows = compact(c, seed, batches)
    p = dict(weights.make(seed, {k: v for k, v in spec.items()
                                 if not k.startswith("embedding/")}),
             **tables)
    p0 = dict(p)
    adagrad = _programs(ref._key(c), prec)[1]
    acc = _filled(p, float(c["optimizer"]["initial_acc"]))
    losses, gnorm = [], None
    for batch, r in zip(batches, rows):
        loss, g = grads(c, p, batch, r, prec, half)
        if gnorm is None:
            gnorm = _floats(_norms(g))
        p, acc = adagrad(p, acc, g)
        losses.append(loss)
    change = _floats(_norms(p, p0))
    return losses, gnorm, change
