"""Closed-loop training on one host's mesh: dlrm-rm2 with full tables,
row-sharded over the chips' 'model' axis, through the program's ``Trainer``
with its guarded step, the sharded row lookup and row-sparse Adagrad.

The trainer is given the mesh (``repro.launch.mesh.parse_mesh`` of the
traffic's ``mesh``) and the program's sharding rules; the weights are drawn
from the seed straight into the tables' padded, sharded layout
(``repro.dist.sharding.placement``), so no chip ever holds a whole table.
Set-up drives the trainer through the check steps with the window's own
call and feed; the window then continues on fresh batches until its time
is up.  After the window the program's state is freed and the reference
(``bench.reference.full``) follows the check steps from the same seed on
one chip.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

# the program's mesh entry point: a program without it fails here, at once
from repro.launch.mesh import parse_mesh

from bench import (checks, harness, ici, mesh_scopes, peaks, scopes,
                   table_cost, trace, weights)
from bench.cells import train as train_cell
from bench.program import generator
from bench.reference import full as ref


def model_config(c: dict):
    """The program's ``RecsysConfig`` with full tables for configuration
    ``c``, checked against every size the file states."""
    from repro.configs.base import get_config
    arch = get_config(c["arch"])
    cfg = (arch.make_model(None, embedding_kind=c["embedding"])
           if c["variant"] == "model"
           else arch.make_smoke(embedding_kind=c["embedding"]))
    e = cfg.embedding
    got = {"model": cfg.model, "embedding": e.kind,
           "vocab_sizes": list(e.vocab_sizes), "d": e.dim,
           "n_dense": cfg.n_dense, "bot_mlp": list(cfg.bot_mlp),
           "top_mlp": list(cfg.top_mlp), "dtype": cfg.dtype,
           "table_scale": float(e.scale_or_default()),
           "optimizer": {"kind": arch.optimizer, "lr": arch.learning_rate}}
    want = {k: c[k] for k in got}
    want["optimizer"] = {k: c["optimizer"][k] for k in ("kind", "lr")}
    if got != want:
        raise SystemExit(f"the program's {c['arch']} is not the configuration "
                         f"file's: program {got}, file {want}")
    return arch, cfg


def _draws(key, spec_items: tuple):
    return {p: weights._draw(key, p, *rule) for p, rule in spec_items}


def params(c: dict, cfg, seed: int, mesh, phases):
    """The program's parameter tree, in its placement on ``mesh``, filled
    with the seed's weights.  A table is drawn in its padded, lane-packed
    shape: a draw's values follow the flat index alone, so its rows are
    the [V, d] draw's, and its padded rows the ones after the last (no id
    addresses them)."""
    from repro.dist import sharding as shd
    from repro.launch.train import row_tables
    from repro.models import recsys
    spec = ref.param_spec(c)
    with phases("init"):
        like = jax.eval_shape(lambda k: recsys.init(k, cfg),
                              jax.random.key(0))
        weights.as_tree({p: jax.ShapeDtypeStruct(s, np.float32)
                         for p, (s, _, _) in spec.items()}, like)
        shapes, shardings = shd.placement(like, mesh, shd.recsys_rules(),
                                          row_tables(cfg))
        shape_of = dict(zip(weights.tree_paths(shapes),
                            jax.tree_util.tree_leaves(shapes)))
        sh_of = dict(zip(weights.tree_paths(shardings),
                         jax.tree_util.tree_leaves(shardings)))
        items = tuple((p, (tuple(shape_of[p].shape), i, float(s)))
                      for p, (_, i, s) in spec.items())
        draw = jax.jit(_draws, static_argnums=(1,),
                       out_shardings={p: sh_of[p] for p in spec})
        flat = draw(weights.base_key(seed), items)
        p = weights.as_tree(flat, shapes)
        jax.block_until_ready(p)
    return p


def build(cell: dict, seed: int, devs, phases):
    """(cfg, gen, trainer, mesh, input_ms): the trainer on its mesh with
    its weights, before its first step."""
    from repro.dist import sharding as shd
    from repro.launch.train import (lookups_per_step, make_optimizer,
                                    row_tables)
    from repro.models import recsys
    from repro.train.trainer import Trainer, TrainerConfig
    c, t = cell["config"], cell["traffic"]
    arch, cfg = model_config(c)
    mesh = parse_mesh("x".join(map(str, t["mesh"])), devs)
    with phases("generator"):
        gen = generator(c, t, seed)
    p = params(c, cfg, seed, mesh, phases)
    batch, input_ms = t["batch"], []

    def batch_fn(step):
        t0 = time.perf_counter()
        with harness.span("bench.input"):
            b = gen.batch(batch, step)
        input_ms.append((time.perf_counter() - t0) * 1e3)
        return b

    trainer = Trainer(
        TrainerConfig(total_steps=0, log_every=0,
                      lookups_per_step=lookups_per_step(cfg, batch)),
        lambda p, b, bufs: recsys.loss_fn(p, cfg, b, bufs), p,
        make_optimizer(arch), batch_fn, loss_args=({},), mesh=mesh,
        rules=shd.recsys_rules(), row_tables=row_tables(cfg))
    return cfg, gen, trainer, mesh, input_ms


@functools.partial(jax.jit, static_argnums=(1,))
def _distances(key, rules: tuple, xs: tuple):
    return [jnp.sqrt(jnp.sum(jnp.square(
        x - weights._draw(key, path, x.shape, init, scale))))
        for (path, init, scale), x in zip(rules, xs)]


def distances(c: dict, seed: int, trainer) -> dict:
    """{path: ||parameter - the seed's weights||} in one call, each weight
    drawn inside its reduction in the parameter's own shape and placement
    (a table's padded rows included: they never change)."""
    flat = train_cell.flat(trainer.params)
    rules = tuple((p, i, float(s)) for p, (_, i, s) in
                  ref.param_spec(c).items())
    out = _distances(weights.base_key(seed), rules,
                     tuple(flat[p] for p, _, _ in rules))
    return {p: float(v) for (p, _, _), v in zip(rules, jax.device_get(out))}


def check_steps(cell, seed, trainer, phases):
    """Steps 1..K (the first compiles): the program's losses, first
    gradient norms and parameter change norms."""
    c, k = cell["config"], cell["traffic"]["check_steps"]
    losses, grad = [], None
    for i in range(k):
        with phases("first_step" if i == 0 else f"check_step_{i + 1}"):
            out = train_cell.step(trainer)
            losses.append(out["loss"])
            if i == 0:
                grad = train_cell.grad_norms(trainer,
                                             c["optimizer"]["initial_acc"])
    with phases("check_change"):
        change = distances(c, seed, trainer)
    return losses, grad, change, out


def reference(cell, gen, seed, prec: str = "highest", half: bool = False):
    """The reference's (losses, first gradient norms, change norms) over
    the check steps' batches."""
    t = cell["traffic"]
    batches = [gen.batch(t["batch"], i) for i in range(t["check_steps"])]
    return ref.train(cell["config"], seed, batches, prec, half)


def step_hlo(trainer, batch) -> str:
    """The compiled HLO text of the trainer's jitted step on its mesh."""
    with trainer.mesh_context():
        return trainer._jit_step.lower(
            trainer.params, trainer.opt_state, trainer.put_batch(batch),
            np.float32(1.0), *trainer.loss_args).compile().as_text()


def table_bytes(c, gen, t, mesh) -> dict:
    """{"table_lookup" | "table_update" | "exchange": {plane: bytes}} one
    step requires of each chip, from the check batches' ids."""
    from repro import obs
    from repro.dist import exchange as exl
    n = int(mesh.shape["model"])
    planes = [f"{trace.DEVICE_PREFIX}{d.id}" for d in mesh.devices.flat]
    stats = [table_cost.rows_per_chip(c, gen.batch(t["batch"], i)["sparse"],
                                      n) for i in range(t["check_steps"])]
    distinct = np.mean([s["distinct"] for s in stats], axis=0)
    lookup = next(k.split(".", 1)[1] for k in obs.totals()
                  if k.startswith("table_lookup."))
    update = exl.resolve_update_exchange(mesh).name
    sent = table_cost.exchange_bytes(c, t["batch"], n, lookup, update)
    return {"table_lookup": {p: table_cost.lookup_bytes(c, t["batch"], u)
                             for p, u in zip(planes, distinct)},
            "table_update": {p: table_cost.update_bytes(c, u)
                             for p, u in zip(planes, distinct)},
            "exchange": dict.fromkeys(planes, sent),
            "strategies": {"lookup": lookup, "update": update}}


class _Tracer(harness.Tracer):
    """The harness's tracer that also keeps ``bench.scopes.rows`` (a four
    chip trace of a window holds millions of op events: read it once),
    and with ``keep_dir`` writes them there (``scope_rows.json.gz``)."""

    def stop(self):
        import gzip
        import json
        import os
        import shutil
        jax.profiler.stop_trace()
        self.scope_rows = scopes.rows(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        if self.keep:
            os.makedirs(self.keep, exist_ok=True)
            with gzip.open(os.path.join(self.keep, "scope_rows.json.gz"),
                           "wt") as f:
                json.dump(self.scope_rows, f)
        # ``bench.trace.events``' rows, read once with the scope rows
        return trace.reduce([
            r[:5] for r in self.scope_rows
            if (r[1] == trace.OPS_LINE if r[0].startswith(trace.DEVICE_PREFIX)
                else r[2].startswith("bench."))])


def run(cell: dict, args, devs, phases, counter, t_process: float):
    c, t = cell["config"], cell["traffic"]
    kind = devs[0].device_kind
    peak = peaks.peaks(kind)
    if args.trace:
        # the cache's key leaves op metadata out by default: a step
        # compiled before its scopes existed would be served without them
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    cfg, gen, trainer, mesh, input_ms = build(cell, args.seed, devs, phases)
    losses, grad, change, out = check_steps(cell, args.seed, trainer, phases)
    setup_s = time.perf_counter() - t_process
    print(f"set-up total: {setup_s:.3f} s; program losses {losses}",
          flush=True)

    tracer = _Tracer(args.trace_dir) if args.trace else None
    counter.active = True
    n_steps, t0 = 0, time.perf_counter()
    deadline = t0 + args.seconds
    with harness.span(trace.WINDOW):
        while time.perf_counter() < deadline:
            with harness.span("bench.step"):
                out = train_cell.step(trainer)
            n_steps += 1
    window_s = time.perf_counter() - t0
    counter.active = False
    reduced = tracer.stop() if tracer else None
    device = harness.device_info(devs)
    rate = n_steps * t["batch"] / window_s
    print(f"window: {n_steps} steps in {window_s:.3f} s, {counter.count} "
          f"compiles, loss {out['loss']!r}", flush=True)
    failed = out["skipped_steps"] + out["rollbacks"]

    ctx = {"examples_per_s": rate, "peak": peak, "chips": len(devs),
           "mesh_flops_per_example": table_cost.train_flops_per_example(c)}
    if args.trace:
        hlo = step_hlo(trainer, gen.batch(t["batch"], 0))
        ctx.update(table_scopes=mesh_scopes.reduce(tracer.scope_rows, hlo),
                   table_bytes=table_bytes(c, gen, t, mesh),
                   ici_bytes_per_s=ici.ici_bytes_per_s(kind))
        print(f"table scopes {ctx['table_scopes']}; strategies "
              f"{ctx['table_bytes']['strategies']}", flush=True)
        # the step's op names are read: the reference needs no metadata
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
    del trainer, out
    gc.collect()

    ref_losses, ref_grad, ref_change = reference(cell, gen, args.seed)
    numbers = checks.train_numbers(losses, grad, change, ref_losses,
                                   ref_grad, ref_change)
    correct, table = checks.verdict(numbers, cell["limits"])
    correct = correct and failed == 0
    metrics = {"setup_s": setup_s, "train_examples_per_s": rate,
               "peak_hbm_gib": device["memory_peak_bytes"] / 2**30}
    harness.report(cell, args.trace, correct, n_steps, failed, metrics, ctx,
                   device, reduced, table)


def readings(cell: dict, seed: int, devs, with_program: bool = True) -> dict:
    """The numbers that decide ``correct`` for one seed, for the cell's
    limits: the program's check steps (``program``), the reference at the
    precision below the configuration's in the program's place
    (``control``), and the reference with half of each batch left out
    (``half``)."""
    phases = harness.Phases()
    out = {"seed": seed}
    if with_program:
        _, gen, trainer, _, _ = build(cell, seed, devs, phases)
        losses, grad, change, _ = check_steps(cell, seed, trainer, phases)
        del trainer
        gc.collect()
    else:
        gen = generator(cell["config"], cell["traffic"], seed)
    r = reference(cell, gen, seed)
    if with_program:
        out["program"] = checks.train_numbers(losses, grad, change, *r)
    out["control"] = checks.train_numbers(
        *reference(cell, gen, seed, prec="high"), *r)
    out["half"] = checks.train_numbers(
        *reference(cell, gen, seed, half=True), *r)
    out["ref_losses"] = r[0]
    return out
