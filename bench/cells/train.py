"""Closed-loop training cells: the program's ``Trainer`` with its guarded
step, sparse Adagrad on the LMA pool and dense Adagrad on the rest.

Set-up builds one trainer and drives it through the check steps with the
window's own call and feed; the window then continues with the same
trainer on fresh batches until its time is up.  After the window the
program's state is freed and the reference follows the check steps from
the same seed.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp

from bench import checks, flops, harness, peaks, program, trace, weights
from bench.reference import recsys as ref

def build(cell: dict, seed: int, phases):
    """(cfg, gen, rows, bufs, trainer, input_ms): the trainer with its
    weights and D' buffers, before its first step."""
    from repro.launch.train import lookups_per_step, make_optimizer
    from repro.models import recsys
    from repro.train.trainer import Trainer, TrainerConfig
    c, t = cell["config"], cell["traffic"]
    arch, cfg = program.model_config(c)
    with phases("generator"):
        gen = program.generator(c, t, seed)
    with phases("dprime_rows"):
        rows = gen.signature_rows(c["n_s"])
    bufs = program.dprime_buffers(cfg, rows, phases)
    params = program.params(c, cfg, seed, phases)
    batch, input_ms = t["batch"], []

    def batch_fn(step):
        t0 = time.perf_counter()
        with harness.span("bench.input"):
            b = program.device_batch(gen.batch(batch, step))
        input_ms.append((time.perf_counter() - t0) * 1e3)
        return b

    trainer = Trainer(
        TrainerConfig(total_steps=0, log_every=0,
                      lookups_per_step=lookups_per_step(cfg, batch)),
        lambda p, b, bufs: recsys.loss_fn(p, cfg, b, bufs), params,
        make_optimizer(arch), batch_fn, loss_args=(bufs,))
    return cfg, gen, rows, bufs, trainer, input_ms

def step(trainer) -> dict:
    """One step through ``Trainer.fit``, the window's call."""
    trainer.cfg.total_steps = trainer.step + 1
    return trainer.fit(log=print)

@functools.partial(jax.jit, static_argnums=(1,))
def _acc_norms(accs, initial_acc: float):
    return [jnp.sqrt(jnp.maximum(jnp.sum(a - initial_acc), 0.0)) for a in accs]


def grad_norms(trainer, initial_acc: float) -> dict:
    """Each leaf's first gradient norm, from its Adagrad accumulator."""
    paths = weights.tree_paths(trainer.params)
    accs = jax.tree_util.tree_leaves(trainer.opt_state)
    assert len(accs) == len(paths) and all(
        a.shape == p.shape for a, p in
        zip(accs, jax.tree_util.tree_leaves(trainer.params))), \
        "optimizer state is not one Adagrad accumulator per parameter"
    return dict(zip(paths, map(float, _acc_norms(accs, initial_acc))))


def flat(tree) -> dict:
    return dict(zip(weights.tree_paths(tree), jax.tree_util.tree_leaves(tree)))

def check_steps(cell, seed, trainer, phases):
    """Steps 1..K (the first compiles): the program's losses, first
    gradient norms and parameter change norms."""
    c, k = cell["config"], cell["traffic"]["check_steps"]
    losses, grad = [], None
    for i in range(k):
        with phases("first_step" if i == 0 else f"check_step_{i + 1}"):
            out = step(trainer)
            losses.append(out["loss"])
            if i == 0:
                grad = grad_norms(trainer, c["optimizer"]["initial_acc"])
    with phases("check_change"):
        change = weights.distances(seed, ref.param_spec(c),
                                   flat(trainer.params))
    return losses, grad, change, out

def reference(cell, gen, rows, seed, prec: str = "highest",
              half: bool = False):
    """The reference's (losses, first gradient norms, change norms) over
    the check steps' batches."""
    c, t = cell["config"], cell["traffic"]
    batches = [gen.batch(t["batch"], i) for i in range(t["check_steps"])]
    slots = [ref.pool_slots(c, rows, b["sparse"]) for b in batches]
    spec = ref.param_spec(c)
    losses, grad, p = ref.train(c, weights.make(seed, spec), batches, slots,
                                prec, half)
    return losses, grad, weights.distances(seed, spec, p)

def time_call(fn, *args, min_s: float = 0.3, min_n: int = 5) -> float:
    """Milliseconds per call of ``fn`` on the device, over enough calls
    to span ``min_s`` of host clock (after one call that compiles)."""
    jax.block_until_ready(fn(*args))
    n, total = 0, 0.0
    while total < min_s or n < min_n:
        k = max(min_n, n)
        t0 = time.perf_counter()
        for _ in range(k):
            out = fn(*args)
        jax.block_until_ready(out)
        total += time.perf_counter() - t0
        n += k
    return total / n * 1e3

def layer_timings(cfg, trainer, bufs, gen, batch: int) -> dict:
    """The embedding lookup alone and one optimizer update alone."""
    from repro.embed import EmbeddingTable
    from repro.models import recsys
    from repro.optim import sparse as sparse_lib
    from repro.optim.optimizers import apply_updates
    b = program.device_batch(gen.batch(batch, 1 << 30))
    tables = [("embedding", cfg.table)]
    if cfg.model == "xdeepfm":
        tables.append(("linear", EmbeddingTable(recsys._linear_cfg(cfg))))

    @jax.jit
    def lookup(p, bufs, sparse):
        return [t.embed_fields(p[k], bufs, sparse) for k, t in tables]

    out = {"lookup_ms": time_call(lookup, trainer.params, bufs, b["sparse"])}
    vg = jax.jit(sparse_lib.sparse_value_and_grad(
        lambda p, x, bufs: recsys.loss_fn(p, cfg, x, bufs)))
    grads = vg(trainer.params, b, bufs)[1]
    opt = trainer.optimizer

    def update(g, s, p):
        u, s = opt.update(g, s, p)
        return apply_updates(p, u), s

    update = jax.jit(update, donate_argnums=(1, 2))
    state = [trainer.opt_state, trainer.params]
    trainer.params = trainer.opt_state = None

    def call(g):
        state[1], state[0] = update(g, state[0], state[1])
        return state[1]

    out["update_ms"] = time_call(call, grads)
    return out

def run(cell: dict, args, devs, phases, counter, t_process: float):
    c, t = cell["config"], cell["traffic"]
    peak = peaks.peaks(devs[0].device_kind)
    cfg, gen, rows, bufs, trainer, input_ms = build(cell, args.seed, phases)
    losses, grad, change, out = check_steps(cell, args.seed, trainer, phases)
    n_check = len(input_ms)
    setup_s = time.perf_counter() - t_process
    print(f"set-up total: {setup_s:.3f} s; program losses {losses}", flush=True)

    tracer = harness.Tracer(args.trace_dir) if args.trace else None
    counter.active = True
    n_steps, t0 = 0, time.perf_counter()
    deadline = t0 + args.seconds
    with harness.span(trace.WINDOW):
        while time.perf_counter() < deadline:
            with harness.span("bench.step"):
                out = step(trainer)
            n_steps += 1
    window_s = time.perf_counter() - t0
    counter.active = False
    reduced = tracer.stop() if tracer else None
    device = harness.device_info(devs)
    rate = n_steps * t["batch"] / window_s
    print(f"window: {n_steps} steps in {window_s:.3f} s, {counter.count} "
          f"compiles, loss {out['loss']!r}", flush=True)
    failed = out["skipped_steps"] + out["rollbacks"]

    step_s = list(trainer._step_times)[-min(n_steps, 256):]
    ctx = {"input_ms": input_ms[n_check:], "step_s": step_s,
           "examples_per_s": rate, "peak": peak, "trace": reduced,
           "flops_per_example": flops.train_flops_per_example(c),
           "lookup_bytes": flops.lookup_bytes(c, t["batch"])}
    if args.trace:
        ctx.update(layer_timings(cfg, trainer, bufs, gen, t["batch"]))
    del trainer, bufs, out
    gc.collect()

    ref_losses, ref_grad, ref_change = reference(cell, gen, rows, args.seed)
    numbers = checks.train_numbers(losses, grad, change, ref_losses,
                                   ref_grad, ref_change)
    correct, table = checks.verdict(numbers, cell["limits"])
    correct = correct and failed == 0
    metrics = {"setup_s": setup_s, "train_examples_per_s": rate,
               "peak_hbm_gib": device["memory_peak_bytes"] / 2**30}
    harness.report(cell, args.trace, correct, n_steps, failed, metrics, ctx,
                   device, reduced, table)
