"""Plain float32 reference of the benchmark's CTR models and their training.

DLRM (arXiv:1906.00091): bottom MLP over the dense features, pairwise dot
products of the bottom output and the field embeddings (lower triangle),
top MLP over [bottom output, dot products].  xDeepFM (arXiv:1803.05170):
a CIN (outer products with the field embeddings, compressed per layer,
sum-pooled over the embedding axis), a DNN over the flattened embeddings,
and a first-order term from a dim-1 embedding of every field; the logit is
their sum.  Binary cross-entropy with logits, averaged over the batch.
Adagrad: ``acc += g**2; p -= lr * g / (sqrt(acc) + eps)``.

Embeddings are gathered from the LMA pool at the slots ``reference.lma``
computes.  Parameters are a flat ``{path: array}`` dict whose paths and
shapes ``param_spec`` derives from the configuration alone.

Every product of two float32 arrays goes through ``matmul``: exact float32
(``highest``), or, for the control, three bfloat16 passes (``high``: each
operand split into a bfloat16 head and tail, the tail-by-tail product
dropped), written out so that it means the same on every backend.
"""
from __future__ import annotations

import functools
import json

import numpy as np

import jax
import jax.numpy as jnp

from bench.reference import lma as ref_lma

HIGHEST = jax.lax.Precision.HIGHEST


def param_spec(c: dict) -> dict:
    """{path: (shape, init, scale)} for configuration ``c``; init is
    ``sign`` (+-scale), ``normal`` (N(0, 1) * scale) or ``zeros``."""
    spec = {"embedding/memory": ((c["lma"]["m"],), "sign", c["memory_scale"])}

    def mlp(name, dims):
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            spec[f"{name}/layer_{i}/kernel"] = ((a, b), "normal", a ** -0.5)
            spec[f"{name}/layer_{i}/bias"] = ((b,), "zeros", 0.0)

    F, d = len(c["vocab_sizes"]), c["lma"]["d"]
    if c["model"] == "dlrm":
        mlp("bot", [c["n_dense"], *c["bot_mlp"]])
        n = F + 1
        mlp("top", [n * (n - 1) // 2 + c["bot_mlp"][-1], *c["top_mlp"]])
    elif c["model"] == "xdeepfm":
        hk = F
        for i, ho in enumerate(c["cin_layers"]):
            spec[f"cin/layer_{i}"] = ((ho, hk, F), "normal", (hk * F) ** -0.5)
            hk = ho
        mlp("cin_out", [sum(c["cin_layers"]), 1])
        spec["cin_out/kernel"] = spec.pop("cin_out/layer_0/kernel")
        spec["cin_out/bias"] = spec.pop("cin_out/layer_0/bias")
        mlp("deep", [F * d, *c["deep_mlp"], 1])
        spec["linear/memory"] = ((c["linear_lma"]["m"],), "sign",
                                 c["memory_scale"])
    else:
        raise ValueError(c["model"])
    return dict(sorted(spec.items()))


def matmul(subscripts: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(subscripts, a, b, precision=HIGHEST)
    assert precision == "high", precision

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    mm = functools.partial(jnp.einsum, subscripts, precision=HIGHEST)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def _mlp(p, name, x, prec, final_relu=False):
    n = sum(1 for k in p if k.startswith(f"{name}/layer_")) // 2
    for i in range(n):
        x = matmul("bi,io->bo", x, p[f"{name}/layer_{i}/kernel"], prec) \
            + p[f"{name}/layer_{i}/bias"]
        if i < n - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def forward(c: dict, p: dict, batch: dict, slots: dict, prec: str):
    """Logits [B].  ``slots``: {pool path: [B, F, d] int32 slots}."""
    B, F = batch["sparse"].shape
    d = c["lma"]["d"]
    feats = p["embedding/memory"][slots["embedding/memory"]]       # [B, F, d]
    if c["model"] == "dlrm":
        bot = _mlp(p, "bot", batch["dense"], prec, final_relu=True)
        allf = jnp.concatenate([bot[:, None, :], feats], axis=1)
        z = matmul("bfd,bgd->bfg", allf, allf, prec)
        ii, jj = np.tril_indices(F + 1, k=-1)
        return _mlp(p, "top", jnp.concatenate([bot, z[:, ii, jj]], -1),
                    prec)[:, 0]
    xk, pools = feats, []
    for i in range(len(c["cin_layers"])):
        outer = xk[:, :, None, :] * feats[:, None, :, :]           # [B,H,F,d]
        xk = jax.nn.relu(matmul("bhfd,ohf->bod", outer, p[f"cin/layer_{i}"],
                                prec))
        pools.append(jnp.sum(xk, axis=-1))
    cin = matmul("bi,io->bo", jnp.concatenate(pools, -1), p["cin_out/kernel"],
                 prec)[:, 0] + p["cin_out/bias"][0]
    deep = _mlp(p, "deep", feats.reshape(B, F * d), prec)[:, 0]
    lin = jnp.sum(p["linear/memory"][slots["linear/memory"]], axis=(1, 2))
    return cin + deep + lin


def loss_sum(c, p, batch, slots, prec, weight):
    """Sum over the block of ``weight`` * per-example cross-entropy."""
    x = forward(c, p, batch, slots, prec)
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

    grad = jax.jit(lambda p, b, s, w: jax.value_and_grad(
        lambda q: loss_sum(c, q, b, s, prec, w))(p))
    return grad, jax.jit(adagrad, donate_argnums=(0, 1))


def _key(c: dict) -> str:
    return json.dumps(c, sort_keys=True)


def pool_slots(c: dict, rows: np.ndarray, sparse: np.ndarray) -> dict:
    """{pool path: [B, F, d] slots} of a batch's field-local ids."""
    offs = np.concatenate([[0], np.cumsum(c["vocab_sizes"])])[:-1]
    gids = (sparse.astype(np.int64) + offs[None, :]).reshape(-1)
    out = {"embedding/memory": c["lma"]}
    if c["model"] == "xdeepfm":
        out["linear/memory"] = c["linear_lma"]
    return {k: jnp.asarray(ref_lma.lookup_slots(v, rows, gids).reshape(
        *sparse.shape, -1)) for k, v in out.items()}


def grads(c: dict, p: dict, batch: dict, slots: dict, prec: str,
          half: bool = False, block: int = 0):
    """(mean loss, {path: dense gradient}) over the batch, ``block`` rows at
    a time.  ``half``: the mean is taken over the first half alone (the
    planted fault of a step that leaves half of its batch out)."""
    B = batch["label"].shape[0]
    n = B // 2 if half else B
    weight = np.where(np.arange(B) < n, 1.0 / n, 0.0).astype(np.float32)
    grad = _programs(_key(c), prec)[0]
    block = block or c["reference_block_rows"]
    loss, total = 0.0, None
    for lo in range(0, B, block):
        sl = slice(lo, min(lo + block, B))
        blk = {k: jnp.asarray(v[sl]) for k, v in batch.items()}
        s = {k: v[sl] for k, v in slots.items()}
        lv, g = grad(p, blk, s, jnp.asarray(weight[sl]))
        loss += float(lv)
        total = g if total is None else jax.tree_util.tree_map(
            jnp.add, total, g)
    return loss, total


def train(c: dict, p: dict, batches: list, slots: list, prec: str,
          half: bool = False):
    """Run Adagrad over ``batches``; returns (losses, first-step gradient
    norm per path, params after the last step).  ``p`` is consumed."""
    adagrad = _programs(_key(c), prec)[1]
    acc = {k: jnp.full_like(v, c["optimizer"]["initial_acc"])
           for k, v in p.items()}
    losses, gnorm = [], None
    for batch, s in zip(batches, slots):
        loss, g = grads(c, p, batch, s, prec, half)
        if gnorm is None:
            gnorm = {k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
                     for k, v in g.items()}
        p, acc = adagrad(p, acc, g)
        losses.append(loss)
    return losses, gnorm, p

