"""The system under test, set up for a cell: the model from the program's
registry, the D' store through the program's own build, and the weights.

The configuration file states the model; ``build`` refuses to run when the
program's registry builds something else, so the configuration is the one
that runs.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import ctr, weights
from bench.reference import recsys as ref


def model_config(c: dict):
    """The program's ``RecsysConfig`` for configuration ``c``, checked
    against every size the file states."""
    from repro.configs.base import get_config
    from repro.models import recsys
    arch = get_config(c["arch"])
    cfg = arch.make_model(None) if c["variant"] == "model" else arch.make_smoke()
    e = cfg.embedding

    def lma_of(ec):
        p = ec.lma
        return {"d": p.d, "m": p.m, "n_h": p.n_h, "max_set": p.max_set,
                "seed": p.seed, "min_support": p.min_support,
                "striped": bool(p.stripe)}

    got = {"model": cfg.model, "vocab_sizes": list(e.vocab_sizes),
           "n_dense": cfg.n_dense, "lma": lma_of(e), "dtype": cfg.dtype,
           "memory_scale": e.init_scale,
           "optimizer": {"kind": arch.optimizer, "lr": arch.learning_rate}}
    want = {k: c[k] for k in got}
    want["optimizer"] = {k: c["optimizer"][k] for k in ("kind", "lr")}
    if cfg.model == "dlrm":
        got.update(bot_mlp=list(cfg.bot_mlp), top_mlp=list(cfg.top_mlp))
    else:
        got.update(cin_layers=list(cfg.cin_layers),
                   deep_mlp=list(cfg.deep_mlp),
                   linear_lma=lma_of(recsys._linear_cfg(cfg)))
    want.update({k: c[k] for k in got if k not in want})
    if got != want or e.kind != "lma" or e.memory_init != "bernoulli":
        raise SystemExit(f"the program's {c['arch']} is not the configuration "
                         f"file's: program {got}, file {want}")
    return arch, cfg


def generator(c: dict, traffic: dict, seed: int) -> ctr.CTR:
    return ctr.CTR(ctr.Spec(tuple(c["vocab_sizes"]), c["n_dense"], seed,
                            **traffic["generator"]))


def dprime_buffers(cfg, rows: np.ndarray, phases) -> dict:
    """D' from ``rows`` through the program's build, densify and buffers."""
    from repro.core.embedding import make_buffers
    from repro.core.signatures import build_signature_store, densify_store
    e = cfg.embedding
    with phases("dprime_build"):
        store = build_signature_store(rows, e.total_vocab,
                                      max_per_value=e.lma.max_set)
    with phases("dprime_densify_to_device"):
        bufs = make_buffers(e, densify_store(store, e.lma.max_set))
        jax.block_until_ready(bufs)
    return bufs


def params(c: dict, cfg, seed: int, phases):
    """The program's parameter tree, filled with the seed's weights."""
    from repro.models import recsys
    with phases("init"):
        like = jax.eval_shape(lambda k: recsys.init(k, cfg), jax.random.key(0))
        p = weights.as_tree(weights.make(seed, ref.param_spec(c)), like)
        jax.block_until_ready(p)
    return p


def device_batch(b: dict) -> dict:
    return {k: jnp.asarray(v) for k, v in b.items()}
