"""On-chip smoke run of LMA training at dlrm-rm2's published widths.

One chip (the default): the training path a user launches —
``repro.launch.train``'s recsys set-up -> ``Trainer`` -> ``recsys.loss_fn``
-> the LMA ``EmbeddingTable`` -> sparse Adagrad — on dlrm-rm2 with nothing
cut (26 Criteo fields, 33,762,577 values, d=64, alpha=16: a 135,053,312-slot
f32 pool, its Adagrad state and a 4.3 GB dense D' store from n_s=125,000
rows).  It takes 8 steps of 4,096 examples, runs one eval forward, and
checks the LMA locations and embeddings of 512 ids against the same
computation on the host CPU backend.

``--chips 4``: the same 8 steps through ``launch.steps.build_cell`` on a
(1, 4) ('data', 'model') mesh with the pool sharded over 'model', against
the no-mesh run on device 0; nothing else runs.

``--smoke`` takes the reduced config and may run off the chip (a CPU
rehearsal); off the chip it prints no result line.

    python chip_smoke.py
    python chip_smoke.py --chips 4
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke

The timings printed come from one smoke run: they are not a benchmark.
The last line on success is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STEPS = 8
BATCH = 4096        # per-chip share of an MLPerf-style DLRM global batch
N_S = 125_000       # D' rows (paper section 7.2)
N_CHECK = 512       # ids whose locations/embeddings are checked on the host
LOSS_RTOL = 1e-5    # mesh vs no-mesh losses


def fail(msg: str):
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak:,} B"


def run_one_chip(arch, cfg, batch: int, n_s: int):
    import jax
    import jax.numpy as jnp

    from repro.embed import get_scheme, resolve_backend
    from repro.launch import train as launch
    from repro.models import recsys
    from repro.train.trainer import Trainer, TrainerConfig

    e = cfg.embedding
    t0 = time.perf_counter()
    gen, bufs, batch_fn, loss_fn = launch.recsys_setup(arch, cfg, n_s, batch)
    params = recsys.init(jax.random.key(0), cfg)
    jax.block_until_ready((params, bufs))
    print(f"set-up (data generator, D' build, init): "
          f"{time.perf_counter() - t0:.1f} s")
    _print_sizes(cfg, params, bufs)
    scheme = get_scheme(e.kind)
    backend = resolve_backend(e, params["embedding"], scheme, bufs)
    print(f"resolve_backend: {backend.name}")

    trainer = Trainer(
        TrainerConfig(total_steps=0, log_every=0,
                      lookups_per_step=launch.lookups_per_step(cfg, batch)),
        loss_fn, params, launch.make_optimizer(arch), batch_fn,
        loss_args=(bufs,))
    print(f"sparse memory-pool updates: {trainer.sparse_grads}")
    out = None
    for step in range(1, STEPS + 1):
        trainer.cfg.total_steps = step
        t = time.perf_counter()
        out = trainer.fit(log=print)
        dt = time.perf_counter() - t
        if step == 1:
            print(f"first step (compile included): {dt:.2f} s")
        print(f"step {step} loss {out['loss']!r}")
        if not np.isfinite(out["loss"]):
            fail(f"non-finite loss at step {step}")
    for k in ("skipped_steps", "rollbacks", "exchange_demotions"):
        if out[k] > 0:
            fail(f"fit() reported {k}={out[k]}")
    print(f"steady step (median of the trainer's {STEPS} step times): "
          f"{1.0 / out['steps_per_sec']:.4f} s — one smoke run, "
          f"not a benchmark")

    fwd = jax.jit(lambda p, b, bufs: recsys.forward(p, cfg, b, bufs))
    eb = gen.batch(batch, 700_000)
    logits = np.asarray(fwd(trainer.params, {
        k: jnp.asarray(v) for k, v in eb.items() if k != "label"}, bufs))
    if logits.shape != (batch,) or not np.isfinite(logits).all():
        fail(f"eval forward gave shape {logits.shape}, "
             f"finite={np.isfinite(logits).all()}")
    print(f"eval forward: {batch} finite logits, mean {float(logits.mean())!r}")

    _check_against_host(cfg, trainer.params["embedding"], bufs, gen, n_s,
                        eb["sparse"])
    print(f"peak device memory: {_peak_bytes(jax.devices()[0])}")


def _print_sizes(cfg, params, bufs):
    e = cfg.embedding
    mem = params["embedding"]["memory"]
    print(f"pool: {mem.shape[0]:,} slots x {mem.dtype} = {mem.nbytes:,} B "
          f"(Adagrad state the same again)")
    for k, v in bufs.items():
        print(f"buffer {k}: {tuple(v.shape)} {v.dtype} = {v.nbytes:,} B")
    print(f"fields {e.n_tables}, values {e.total_vocab:,}, d={e.dim}, "
          f"n_h={e.lma.n_h}, max_set={e.lma.max_set}, "
          f"striped={e.lma.striped}")


def _check_against_host(cfg, emb_params, bufs, gen, n_s, sparse):
    """LMA locations + embeddings of N_CHECK ids on the default device ==
    the same allocation run on the host CPU backend from a D' store rebuilt
    there, gathered from a host copy of the trained pool."""
    import jax
    import jax.numpy as jnp

    from repro.core.allocation import alloc_lma_from_rows
    from repro.core.signatures import DenseSignatureStore, build_signature_store
    from repro.embed import get_scheme, resolve_backend

    e = cfg.embedding
    scheme = get_scheme(e.kind)
    rng = np.random.default_rng(0)
    # half seen in the data (D' rows), half uniform (mostly the very-sparse
    # fallback)
    seen = (sparse.astype(np.int64)
            + np.asarray(e.table_offsets()[:-1])[None, :]).reshape(-1)
    ids = np.concatenate([rng.choice(seen, N_CHECK // 2, replace=False),
                          rng.integers(0, e.total_vocab, N_CHECK // 2)])
    ids = ids.astype(np.int32)

    def chip_fn(mem, b, g):
        p = {"memory": mem}
        backend = resolve_backend(e, p, scheme, b)
        return scheme.locations(e, b, g), backend.lookup(e, scheme, p, b, g)

    loc_dev, emb_dev = jax.jit(chip_fn)(emb_params["memory"], bufs,
                                        jnp.asarray(ids))
    loc_dev, emb_dev = np.asarray(loc_dev), np.asarray(emb_dev)

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        store = build_signature_store(gen.rows_for_signatures(n_s),
                                      e.total_vocab,
                                      max_per_value=e.lma.max_set)
    flat, offs, lens = (np.asarray(x) for x in
                        (store.flat, store.offsets, store.lengths))
    rows = np.full((ids.size, e.lma.max_set), DenseSignatureStore.PAD,
                   np.uint32)
    for i, g in enumerate(ids):
        rows[i, :lens[g]] = flat[offs[g]:offs[g] + lens[g]]
    support = np.minimum(lens[ids], e.lma.max_set).astype(np.int32)
    put = lambda x: jax.device_put(x, cpu)
    loc_host = np.asarray(jax.jit(
        lambda r, s, g: alloc_lma_from_rows(e.lma, r, s, g))(
            put(rows), put(support), put(ids)))
    emb_host = np.asarray(jax.device_get(emb_params["memory"]))[loc_host]
    n_fb = int((support < e.lma.min_support).sum())
    if not np.array_equal(loc_dev, loc_host):
        fail(f"locations differ from the host CPU backend at "
             f"{int((loc_dev != loc_host).sum())} of {loc_host.size} entries")
    if not np.array_equal(emb_dev, emb_host):
        fail(f"embeddings differ from the host CPU backend at "
             f"{int((emb_dev != emb_host).sum())} of {emb_host.size} entries")
    print(f"host check: {ids.size} ids ({n_fb} on the very-sparse fallback): "
          f"locations and embeddings equal to the host CPU backend's")


def run_four_chips(arch, cfg, batch: int, n_s: int, smoke: bool):
    import jax

    from repro.dist.context import use_mesh
    from repro.launch import steps
    from repro.launch import train as launch
    from repro.launch.mesh import make_mesh
    from repro.models import recsys

    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs)
    gen, bufs, batch_fn, _ = launch.recsys_setup(arch, cfg, n_s, batch)
    bundle = steps.build_cell(arch.arch_id, "train_batch", mesh, batch=batch,
                              smoke=smoke)
    meta = bundle.meta
    print(f"mesh {dict(mesh.shape)}; exchange {meta.get('exchange')}; "
          f"sparse_grads {meta.get('sparse_grads')}")
    optimizer = steps.make_optimizer(arch)
    p_sh, o_sh, b_sh, x_sh = bundle.in_shardings

    def run(sharded: bool):
        params = recsys.init(jax.random.key(0), cfg)
        opt_state = optimizer.init(params)
        bf = bufs
        if sharded:
            params = jax.device_put(params, p_sh)
            opt_state = jax.device_put(opt_state, o_sh)
            bf = jax.device_put(bufs, b_sh)
            mem = params["embedding"]["memory"]
            shards = {s.device: s.data.shape for s in mem.addressable_shards}
            if (len(shards) != 4
                    or set(shards.values()) != {(mem.shape[0] // 4,)}):
                fail(f"pool not split over four devices: {shards}")
            print(f"pool shards: {len(shards)} devices x "
                  f"{mem.shape[0] // 4:,} slots")
            step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                           out_shardings=bundle.out_shardings,
                           donate_argnums=bundle.donate)
        else:
            step = jax.jit(bundle.fn, donate_argnums=bundle.donate)
        losses, times = [], []
        for i in range(STEPS):
            b = batch_fn(i)
            if sharded:
                b = jax.device_put(b, x_sh)
            t = time.perf_counter()
            with use_mesh(mesh if sharded else None):
                params, opt_state, loss = step(params, opt_state, bf, b)
            loss = float(loss)
            times.append(time.perf_counter() - t)
            losses.append(loss)
            if not np.isfinite(loss):
                fail(f"non-finite loss at step {i + 1}")
        name = "mesh (1, 4)" if sharded else "no mesh, device 0"
        print(f"{name}: first step (compile included) {times[0]:.2f} s, "
              f"steady step (median) {float(np.median(times[1:])):.4f} s "
              f"— one smoke run, not a benchmark")
        return losses

    mesh_losses = run(sharded=True)
    ref_losses = run(sharded=False)
    for i, (a, r) in enumerate(zip(mesh_losses, ref_losses)):
        rel = abs(a - r) / max(abs(r), 1e-30)
        print(f"step {i + 1} loss mesh {a!r} no-mesh {r!r} rel {rel:.3e}")
        if rel > LOSS_RTOL:
            fail(f"step {i + 1}: mesh loss differs by {rel:.3e} relative")
    for d in devs:
        print(f"peak device memory {d}: {_peak_bytes(d)}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config; may run off the chip")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)   # lines survive a cut run

    import jax
    platform = jax.default_backend()
    if platform != "tpu" and not args.smoke:
        fail(f"the default JAX backend is {platform!r}, not 'tpu'")

    from repro.configs.base import get_config
    from repro.kernels import dispatch
    from repro.launch.compile_cache import setup_compile_cache

    cache = setup_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}")
    for line in dispatch.describe():
        print(f"dispatch: {line}")
    arch = get_config("dlrm-rm2")
    cfg = arch.make_smoke() if args.smoke else arch.make_model(None)
    batch, n_s = (256, 2000) if args.smoke else (BATCH, N_S)
    print(f"config {cfg.name}: batch {batch}, n_s {n_s}, {STEPS} steps")
    if args.chips == 4:
        run_four_chips(arch, cfg, batch, n_s, args.smoke)
    else:
        run_one_chip(arch, cfg, batch, n_s)
    if platform != "tpu":
        print(f"rehearsal passed on {platform}; no result line off the chip")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
