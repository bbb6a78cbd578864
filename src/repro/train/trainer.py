"""Generic self-healing training loop.

Works for every model family in the repo: the caller supplies
``loss_fn(params, batch) -> (loss, metrics)`` and a host batch iterator.

Fault-tolerance posture (1000+-node design, exercised at container scale):
  * periodic + on-preemption checkpointing through CheckpointManager (atomic,
    async) — SIGTERM/SIGINT triggers a final save before exit; a *second*
    signal restores the default handler so a hung save can still be killed;
    ``ckpt_delta=True`` switches to incremental checkpoints: only the pool
    chunks dirtied since the last durable step are persisted (the dirty set
    is fed by the step's SparseGrad indices, or by the tier controller's
    planned touch set), compacted back to a full base every
    ``ckpt_compact_every`` deltas;
  * resume: ``fit`` restores the latest checkpoint (params, opt state, step,
    data cursor) if one exists, so a killed run continues exactly where it
    was; a corrupt latest falls back to the previous retained step
    (``CheckpointManager.restore``);
  * guarded step (``repro.resilience.guard``): an in-jit all-finite +
    magnitude check over loss and gradients — dense leaves and SparseGrad
    values alike.  A poisoned step is *skipped* via ``lax.cond`` (params,
    opt_state and every moment bit-untouched), counted in ``health``;
    ``max_consecutive_skips`` skips in a row trigger a rollback to the last
    checkpoint with bounded exponential backoff.  ``REPRO_GUARD_STEP=0`` or
    ``TrainerConfig.guard_step=False`` restores the unguarded fast path;
  * pool integrity (``repro.resilience.integrity``): the memory pool is
    scanned on-device every ``ckpt_every`` steps and after every restore;
    chunks holding bit-rot signatures (non-finite / overflow-scale values)
    are quarantined — zeroed, which LMA's shared-memory formulation degrades
    under gracefully — and counted in ``health.quarantined_chunks``;
  * fault injection (``repro.resilience.faults``): a seeded injector
    (``REPRO_FAULTS`` / the ``faults=`` ctor arg) drives every one of the
    paths above deterministically in tests;
  * straggler telemetry: per-step wall time ring buffer; steps slower than
    ``straggler_factor`` x median are counted and reported (on a real mesh
    this feeds the re-mesh decision — in SPMD a persistent straggler is
    replaced by checkpoint-restart onto a healthy slice, which is exactly
    the elastic restore path tested in tests/test_fault_tolerance.py);
  * data pipeline is index-based (seekable), so restarts do not replay or
    skip batches, and a skipped step still advances the cursor (the faulted
    batch is dropped, not retried forever);
  * input prefetch: while step s runs on the device, one worker thread
    makes (and on a mesh puts) batch s + 1, across ``fit`` calls.  A
    trainer with a tier controller draws each batch inline instead: its
    ``pre_step`` installs the pool that the batch's remap buffers match.

``fit`` returns one unified result dict on every exit path — step, loss,
preempted flag, the full health counter set, and throughput stats.
"""
from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import signal
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.dist import sharding as shd
from repro.dist.context import use_mesh
from repro.optim import sparse as sparse_lib
from repro.optim.optimizers import Optimizer
from repro.resilience import faults as faults_lib
from repro.resilience import guard as guard_lib
from repro.resilience import integrity as integ_lib
from repro.resilience.health import Health

# host spans of one step besides the batch and the device wait: the
# periodic log line's "host" share
HOST_SPANS = ("train.tier", "train.dispatch", "train.sync",
              "train.bookkeeping")
# the pool-update paths the sparse optimizers tally as they trace
POOL_PATHS = ("pool_update.stripe_blocked", "pool_update.gather_scatter")
# the full tables' counters: the lookup's exchange, the update's path
TABLE_COUNTERS = ("table_lookup.", "table_update.")


def throughput_stats(step_times, lookups_per_step: int = 0,
                     tier_stats: dict | None = None) -> dict:
    """One throughput definition for trainer logs AND the kernel bench:
    median step wall-time -> steps/s, scaled by the embedding-row lookups a
    step performs (0 when unknown).  ``tier_stats`` (a
    ``TierController.stats()`` dict, when the pool is tiered) adds the
    host-traffic view: staged cold blocks and host-fetch bytes averaged
    per staging step, plus the hot/cold row split."""
    if not len(step_times):
        out = {"steps_per_sec": 0.0, "lookups_per_sec": 0.0}
    else:
        sps = 1.0 / max(float(np.median(np.asarray(step_times))), 1e-12)
        out = {"steps_per_sec": sps,
               "lookups_per_sec": sps * lookups_per_step}
    if tier_stats:
        n = max(tier_stats.get("stage_steps", 0), 1)
        out.update({
            "tier_hot_rows": tier_stats.get("hot_rows", 0),
            "tier_cold_rows": tier_stats.get("cold_rows", 0),
            "tier_staged_blocks_per_step":
                tier_stats.get("staged_blocks", 0) / n,
            "tier_host_fetch_bytes_per_step":
                tier_stats.get("host_fetch_bytes", 0) / n,
            "tier_promoted": tier_stats.get("promoted", 0),
            "tier_demoted": tier_stats.get("demoted", 0),
        })
    return out


@dataclasses.dataclass
class _Ahead:
    """A batch being made one step ahead: its step, the ``batch_fn`` that
    makes it, the preempt flag as it stood when it was submitted, and the
    worker's future (its result, or the exception ``batch_fn`` raised)."""
    step: int
    batch_fn: Callable
    preempted: bool
    future: concurrent.futures.Future


def _restore_like(template, restored):
    """Rebuild ``restored`` (structure-lossy after serialization) into the tree
    structure of ``template`` (NamedTuples, custom nodes)."""
    leaves = jax.tree_util.tree_leaves(restored)
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 200
    keep: int = 3
    log_every: int = 50
    straggler_factor: float = 3.0
    async_ckpt: bool = True
    # embedding-row lookups one step performs (B * F for field models);
    # feeds the lookups_per_sec throughput stat when set
    lookups_per_step: int = 0
    # --- durability ---
    ckpt_delta: bool = False            # incremental (delta) checkpoints
    ckpt_compact_every: int = 8         # deltas before forcing a full base
    # --- resilience ---
    guard_step: Optional[bool] = None   # None -> REPRO_GUARD_STEP (default on)
    max_abs_grad: float = guard_lib.MAX_ABS_GRAD
    max_consecutive_skips: int = 3      # skips in a row before rollback
    rollback_backoff: float = 0.05      # first rollback wait (seconds)
    rollback_backoff_max: float = 5.0   # backoff ceiling
    max_rollbacks: int = 8              # then give up (RuntimeError)
    verify_pool: bool = True            # integrity scan at ckpt boundaries
    # roll back (instead of training on zeroed rows) when the boundary scan
    # quarantines fresh corruption and a checkpoint exists — the bit-rot
    # twin of the skip-streak rollback, restores true bytes instead of zeros
    rollback_on_quarantine: bool = False


class Trainer:
    def __init__(self, cfg: TrainerConfig, loss_fn: Callable, params,
                 optimizer: Optimizer, batch_fn: Callable[[int], dict],
                 donate: bool = True, sparse_grads: bool | None = None,
                 faults: faults_lib.FaultInjector | None = None,
                 tier=None, loss_args: tuple = (), mesh=None,
                 rules: list = (), row_tables: str | None = None):
        """``batch_fn(step) -> host batch dict`` (seekable by step).  It
        may be called one step ahead, from a worker thread, and once past
        ``total_steps``, so it must be seekable and thread-safe: a batch
        made ahead that no step takes is dropped, and an exception it
        raised is re-raised only by the step that takes it.  The trainer
        reads ``self.batch_fn`` each time it draws a batch.

        ``mesh``: a ('data', 'model') mesh (``repro.launch.mesh``) to train
        on.  ``params`` are then placed by the sharding ``rules`` (for a
        recsys model ``repro.dist.sharding.recsys_rules``), the leaves
        ``row_tables`` matches (the full baseline's,
        ``sharding.ROW_TABLES``) padded and lane-packed, the optimizer
        state follows its parameters' placement, each batch is
        put on the mesh (rows over the data-parallel axes when they divide
        it, else replicated), and the step is traced and run under
        ``repro.dist.context.use_mesh``, so the embedding lookups take
        their sharded paths.

        ``loss_args``: extra arguments of ``loss_fn(params, batch,
        *loss_args)``, passed to the jitted step on every call (never
        closed over) — the embedding buffers of a recsys model.

        ``tier``: a :class:`repro.tier.training.TierController` when the
        memory pool exceeds the per-device budget.  The trainer then runs
        the controller's between-steps hook (writeback -> re-tier -> stage
        -> install) before fetching each batch, and draws batches through
        the controller so the per-step tier remap buffers ride along.
        The checkpointed state is the reconstructed *full* pool (values and
        moments, via ``TierController.export_full``) plus the tier meta
        (hot set + touch-count EMA), so a restore rebuilds the host mirror,
        hot slab and EMA bit-exactly and a rollback composes with tiering
        (staged rows of the abandoned timeline are dropped, the mirror
        adopts the checkpointed bytes).

        ``sparse_grads=None`` auto-enables the sparse-gradient pipeline
        (``repro.optim.sparse``) when the gate is on and the params hold a
        memory pool: the pool's gradient is a SparseGrad over the K touched
        slots and the optimizers route it to the O(K) lazy update — exact
        for Adagrad / momentum-less SGD.  ``REPRO_SPARSE_GRADS=0`` (or
        ``sparse_grads=False``) keeps the dense O(m) path as the oracle.

        ``faults=None`` builds an injector from ``REPRO_FAULTS`` when set;
        pass an explicit :class:`repro.resilience.faults.FaultInjector` to
        drive fault drills programmatically.
        """
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.loss_args = tuple(loss_args)
        self.optimizer = optimizer
        self.mesh = mesh
        if mesh is not None:
            params = shd.place(params, mesh, rules, row_tables)
        self.params = params
        # on a mesh each state leaf is made like its committed parameter
        # (``full_like`` / ``zeros_like`` keep the parameter's sharding)
        self.opt_state = optimizer.init(params)
        self.tier = tier
        self.batch_fn = tier.batch_fn if tier is not None else batch_fn
        self.step = 0
        self.mgr = (CheckpointManager(cfg.ckpt_dir, cfg.keep,
                                      delta=cfg.ckpt_delta,
                                      compact_every=cfg.ckpt_compact_every)
                    if cfg.ckpt_dir else None)
        self._resumed_step: int | None = None
        self._preempted = False
        self._step_times: collections.deque[float] = collections.deque(
            maxlen=256)
        # the input worker (started on the first prefetch) and its batch
        self._input_pool: concurrent.futures.ThreadPoolExecutor | None = None
        self._ahead: _Ahead | None = None
        self._log_totals = obs.totals()
        self.health = Health()
        self._consecutive_skips = 0
        self.faults = faults if faults is not None else faults_lib.from_env()
        if faults is not None:
            faults_lib.install(faults)  # manager/driver hooks see it too
        if sparse_grads is None:
            sparse_grads = (sparse_lib.sparse_enabled()
                            and sparse_lib.has_sparse_leaves(params))
        self.sparse_grads = sparse_grads
        self._has_pool = sparse_lib.has_memory(params)
        self.guard = (cfg.guard_step if cfg.guard_step is not None
                      else guard_lib.guard_enabled())
        # delta checkpoints over a resident sparse pool: the step reports
        # its SparseGrad slot indices so the manager can mark dirty chunks
        # (tiered runs feed the dirty set from pre_step's planned touches)
        self._touched_out = bool(self.mgr is not None and self.mgr.delta
                                 and sparse_grads and tier is None)
        self._jit_step = guard_lib.make_step(
            loss_fn, optimizer, sparse_grads=sparse_grads, guard=self.guard,
            donate=donate, max_abs_grad=cfg.max_abs_grad,
            report_touched=self._touched_out)

    # back-compat: straggler count predates the Health record
    @property
    def straggler_steps(self) -> int:
        return self.health.straggler_steps

    @straggler_steps.setter
    def straggler_steps(self, v: int):
        self.health.straggler_steps = v

    # ------------------------------------------------------------ preemption
    def install_signal_handlers(self):
        def handler(signum, frame):
            if self._preempted:
                # second signal: the graceful path is presumably hung on a
                # save — give the user back a killable process
                signal.signal(signum, signal.SIG_DFL)
                return
            self._preempted = True

        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)

    def preempt(self):
        """Simulate a preemption notice (tests call this directly)."""
        self._preempted = True

    # ----------------------------------------------------------- checkpoints
    def _state(self):
        state = {"params": self.params, "opt_state": self.opt_state,
                 "step": jnp.asarray(self.step, jnp.int32)}
        if self.tier is not None:
            # durable cold tier: persist the reconstructed FULL pool (values
            # + moments) and the tier meta, not the transient compact view —
            # numpy leaves keep the EMA's float64 bits through np.savez
            state["params"], state["opt_state"] = self.tier.export_full(
                self.params, self.opt_state)
            state["tier"] = self.tier.tier_meta()
        return state

    def save(self, blocking: bool = True):
        if self.mgr:
            self.mgr.save(self.step, self._state(),
                          blocking=blocking or not self.cfg.async_ckpt)

    def try_resume(self) -> bool:
        if not self.mgr:
            return False
        # an in-flight async save must land before we look for "latest" —
        # otherwise restore races the writer (and can read a half-renamed dir)
        self.mgr.wait()
        if self.mgr.latest_step() is None:
            return False
        _, state = self.mgr.restore()
        # serialization flattens NamedTuples (AdamState etc.) to plain tuples;
        # rebuild into the live templates' tree structure (leaf shapes may
        # legitimately differ: tiered checkpoints hold full pools)
        self.params = _restore_like(self.params, state["params"])
        self.opt_state = _restore_like(self.opt_state, state["opt_state"])
        self.step = int(np.asarray(state["step"]))
        self._resumed_step = self.step
        self._ahead = None          # the next step's batch is made inline
        if self.tier is not None:
            meta = state.get("tier") if isinstance(state, dict) else None
            if meta is not None:
                # durable cold tier: mirror + hot set + EMA adopt the
                # checkpointed bytes, staged rows of the abandoned timeline
                # are dropped, and we get the compact device view back
                self.params, self.opt_state = self.tier.on_restore(
                    self.params, self.opt_state, meta)
            else:
                # legacy compact checkpoint: pre-durability behavior
                self.tier.on_restore()
        report = self.mgr.last_restore_report
        self.health.quarantined_chunks += report.get("quarantined_chunks", 0)
        self.health.torn_writes_detected += report.get("torn_writes", 0)
        if self.cfg.verify_pool and self._has_pool:
            self._verify_pool()
        return True

    # ------------------------------------------------------------------- fit
    def fit(self, log: Callable[[str], None] = print) -> dict:
        """Train to ``cfg.total_steps``.  Every boundary of the loop is an
        ``repro.obs`` span (``train.resume``, then per step ``train.tier``,
        ``train.batch``, ``train.dispatch``, ``train.wait``, ``train.sync``,
        ``train.bookkeeping`` under the step's ``train.step``, and
        ``train.result``), so a profile names the host's share of a step.

        Once step s is dispatched, ``batch_fn(s + 1)`` runs on the input
        worker (span ``input.prefetch``) while the device works, also when
        s + 1 is ``total_steps``, so that the next call's first step finds
        its batch made.  ``train.batch`` then only takes it (counter
        ``input.prefetch.hit``); a step whose batch was not made ahead --
        the first, one after a resume or rollback, every step of a tiered
        trainer -- makes it inline (``input.prefetch.miss``).  The fault
        injector's ``pre_step`` runs before the step takes its batch on
        either path.  A step whose batch was made ahead
        reads the preempt flag as it stood when that batch was submitted,
        so a flag raised by ``batch_fn(s)`` still stops the run at s + 1,
        and a signal is honoured at most one step later."""
        with obs.span("train.resume"):
            resumed = self.try_resume()
        if resumed:
            log(f"[trainer] resumed from step {self.step}")
        last_loss = float("nan")
        while self.step < self.cfg.total_steps:
            with obs.step_span("train.step", self.step):
                inline = self.tier is not None
                ahead = None if inline else self._take_ahead()
                if self._preempted if ahead is None else ahead.preempted:
                    log(f"[trainer] preempted at step {self.step}; "
                        f"checkpointing")
                    with obs.span("train.checkpoint"):
                        self.save(blocking=True)
                    return self._result(last_loss, preempted=True)
                if self.faults or self.tier is not None:
                    with obs.span("train.tier"):
                        self._pre_step()
                    if self._preempted:
                        continue
                with obs.span("train.batch"):
                    if ahead is not None:
                        obs.count("input.prefetch.hit")
                        batch = ahead.future.result()
                    else:
                        obs.count("input.prefetch.miss")
                        batch = self._make_batch(self.batch_fn, self.step)
                fault = (self.faults.grad_fault(self.step) if self.faults
                         else 1.0)
                delay = (self.faults.step_delay(self.step) if self.faults
                         else 0.0)
                with obs.span("train.dispatch") as dispatch:
                    if delay:
                        time.sleep(delay)  # inside the timed region
                    with self.mesh_context():
                        out = self._jit_step(self.params, self.opt_state,
                                             batch, np.float32(fault),
                                             *self.loss_args)
                    (self.params, self.opt_state, loss, metrics, ok,
                     grads_ok) = out[:6]
                if not inline:
                    self._submit_ahead(self.step + 1)
                with obs.span("train.wait") as wait:
                    loss.block_until_ready()
                # dispatch to ready, an injected delay included: the
                # straggler ring buffer and the throughput stats read it
                dt = (wait.t1 - dispatch.t0) / 1e9
                with obs.span("train.sync"):
                    if bool(ok):
                        self._consecutive_skips = 0
                        last_loss = float(loss)
                        if self._touched_out:
                            # resident sparse feed: this step's SparseGrad
                            # indices (skipped steps touch nothing, so only
                            # marked on ok)
                            self.mgr.mark_dirty_slots(np.asarray(out[6]))
                    else:
                        self.health.skipped_steps += 1
                        if not bool(grads_ok):
                            self.health.nonfinite_grads += 1
                        self._consecutive_skips += 1
                        log(f"[trainer] step {self.step} non-finite; "
                            f"skipped (state untouched, "
                            f"{self._consecutive_skips} in a row)")
                with obs.span("train.bookkeeping"):
                    self._track_straggler(dt)
                    self.step += 1
                    if (self.cfg.log_every
                            and self.step % self.cfg.log_every == 0):
                        self._log_step(log, last_loss, dt)
                    if (self._consecutive_skips
                            >= self.cfg.max_consecutive_skips):
                        self._rollback(log)
                        continue
                    if (self.cfg.ckpt_every
                            and self.step % self.cfg.ckpt_every == 0):
                        if self._boundary(log):
                            continue
        if self.mgr:
            with obs.span("train.checkpoint"):
                self.save(blocking=True)
                self.mgr.wait()
        return self._result(last_loss, preempted=False)

    def mesh_context(self):
        """``use_mesh`` of the trainer's mesh (nothing without one): the
        context its step is traced and run in, for eval calls alike."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return use_mesh(self.mesh)

    def _make_batch(self, batch_fn: Callable, step: int) -> dict:
        batch = batch_fn(step)
        return batch if self.mesh is None else self.put_batch(batch)

    def _prefetch(self, batch_fn: Callable, step: int) -> dict:
        with obs.span("input.prefetch"):
            return self._make_batch(batch_fn, step)

    def _submit_ahead(self, step: int):
        """Start making ``step``'s batch on the input worker."""
        if self._input_pool is None:
            self._input_pool = concurrent.futures.ThreadPoolExecutor(
                1, thread_name_prefix="trainer-input")
        fn = self.batch_fn
        self._ahead = _Ahead(step, fn, self._preempted,
                             self._input_pool.submit(self._prefetch, fn, step))

    def _take_ahead(self) -> _Ahead | None:
        """The batch made ahead for this step, or None when the one made
        ahead is for another step or another ``batch_fn`` (dropped
        unwaited)."""
        ahead, self._ahead = self._ahead, None
        if (ahead is not None and ahead.step == self.step
                and ahead.batch_fn is self.batch_fn):
            return ahead
        return None

    def put_batch(self, batch: dict) -> dict:
        """A batch on the mesh: rows over the data-parallel axes when they
        divide them, else replicated."""
        def put(x):
            spec = shd.resolve_template([[shd.DP, "data", None]], x.shape,
                                        self.mesh)
            return jax.device_put(x, NamedSharding(self.mesh, spec))
        return {k: put(v) for k, v in batch.items()}

    def _pre_step(self):
        """The fault injector's and the tier controller's between-steps
        hooks, before the step takes its batch."""
        if self.faults:
            self.faults.pre_step(self, self.step)
            if self._preempted:
                return
        if self.tier is not None:
            # writeback previous stage -> re-tier on cadence -> plan +
            # stage this step's cold blocks (async device_put) ->
            # install the new compact pool.  Runs before batch_fn so
            # the remap buffers in the batch match the installed pool.
            self.params, self.opt_state, tinfo = self.tier.pre_step(
                self.step, self.params, self.opt_state)
            if self.mgr is not None and self.mgr.delta:
                # the planned touch set is exactly what writeback will
                # commit — the tiered feed of the delta dirty set
                self.mgr.mark_dirty_slots(tinfo.get("touched_slots", ()))

    def _boundary(self, log) -> bool:
        """The checkpoint boundary: the pool scan, then an async save.
        True when the scan's fresh corruption rolled the run back."""
        if self.cfg.verify_pool and self._has_pool:
            before = self.health.quarantined_chunks
            with obs.span("train.verify_pool"):
                self._verify_pool(log)
            if (self.cfg.rollback_on_quarantine
                    and self.health.quarantined_chunks > before
                    and self.mgr
                    and self.mgr.latest_step() is not None):
                # fresh corruption at the boundary: restoring the
                # true bytes beats persisting zeroed rows — replay
                # from the last durable step instead of saving
                log(f"[trainer] step {self.step}: boundary scan "
                    f"quarantined fresh corruption; rolling back")
                self._rollback(log)
                return True
        if self.mgr:
            with obs.span("train.checkpoint"):
                self.save(blocking=False)
        return False

    def _log_step(self, log, last_loss: float, dt: float):
        """The periodic line: loss, the last step's time, throughput, the
        host's split since the previous line from the span totals (mean
        ``train.batch`` and mean other host spans per step, ms, and the
        steps whose batch was made ahead), the pool-update paths of the
        pool leaves and the full tables' lookup exchange and update paths
        traced since then."""
        tp = self.throughput()
        lk = (f" {tp['lookups_per_sec']:,.0f} lookups/s"
              if self.cfg.lookups_per_step else "")
        now = obs.totals()
        prev, self._log_totals = self._log_totals, now

        def since(name):
            a, b = now.get(name, {}), prev.get(name, {})
            return (a.get("s", 0.0) - b.get("s", 0.0),
                    a.get("calls", 0) - b.get("calls", 0))

        steps = max(since("train.batch")[1], 1)     # steps since the last line
        hits = since("input.prefetch.hit")[1]
        host = sum(since(n)[0] for n in HOST_SPANS)
        hb = self.health.summary()
        paths = ", ".join(f"{n.split('.')[1]} x{since(n)[1]}"
                          for n in POOL_PATHS if since(n)[1])
        tables = ", ".join(f"{n} x{since(n)[1]}" for n in sorted(now)
                           if n.startswith(TABLE_COUNTERS) and since(n)[1])
        log(f"[trainer] step {self.step} loss {last_loss:.4f} "
            f"({dt*1e3:.1f} ms, {tp['steps_per_sec']:.1f} steps/s{lk}; "
            f"batch {since('train.batch')[0] / steps * 1e3:.1f} ms, "
            f"host {host / steps * 1e3:.1f} ms per step; "
            f"prefetched {hits}/{steps})"
            + (f" [pool update traced: {paths}]" if paths else "")
            + (f" [tables traced: {tables}]" if tables else "")
            + (f" [health: {hb}]" if hb else ""))

    def _result(self, last_loss: float, preempted: bool) -> dict:
        with obs.span("train.result"):
            return self._result_dict(last_loss, preempted)

    def _result_dict(self, last_loss: float, preempted: bool) -> dict:
        # one constructor for every exit path: the preempted dict used to
        # silently drop straggler_steps (and would have dropped the health
        # counters), breaking dashboards that key on them.  guard_enabled +
        # the resolved exchange make bench rows / logs self-describing —
        # health counters without the mode that produced them were ambiguous
        from repro.dist import exchange as exchange_lib
        self._sync_durability()
        return {"step": self.step, "loss": last_loss, "preempted": preempted,
                "guard_enabled": bool(self.guard),
                "resumed_step": self._resumed_step,
                "exchange": exchange_lib.effective(exchange_lib.FORCED)
                if exchange_lib.FORCED else "auto",
                **self.health.as_dict(), **self.throughput()}

    def _sync_durability(self):
        """Copy the checkpoint manager's durability gauges into the health
        record (surfaced by ``fit``'s result dict and the periodic logs)."""
        if self.mgr is None:
            return
        last = self.mgr.last_saved_step
        if last is None:
            last = self.mgr._last_step     # restored-but-not-yet-saved
        if last is not None:
            self.health.last_durable_step = int(last)
        self.health.ckpt_bytes_written = int(self.mgr.bytes_written)
        self.health.delta_chain_len = int(self.mgr.chain_len)

    # ------------------------------------------------------------ resilience
    def _verify_pool(self, log: Callable[[str], None] = print):
        """On-device integrity scan over every memory leaf; quarantine
        (zero) chunks carrying bit-rot signatures.  Zero rows degrade
        gracefully under LMA — callers measure the accuracy dent instead of
        crashing (tests/test_resilience.py does, on the CTR smoke model).
        The optimizer's pool moments are scanned too: a rotten accumulator
        chunk poisons every later update it scales (a zeroed one merely
        restarts accumulation)."""
        self.params, n_bad = integ_lib.sanitize_tree(self.params)
        self.opt_state, n_bad_opt = integ_lib.sanitize_tree(self.opt_state)
        n_bad += n_bad_opt
        if self.tier is not None:
            # the host-cold tier never visits the device, so the on-device
            # scan cannot see it — run the numpy twin over the host mirror
            n_bad += self.tier.store.sanitize_cold()
        if n_bad:
            self.health.quarantined_chunks += n_bad
            log(f"[trainer] pool integrity: quarantined {n_bad} corrupt "
                f"chunk(s) at step {self.step}")

    def _rollback(self, log: Callable[[str], None] = print):
        """K consecutive skipped steps: restore the last checkpoint and
        retry from there, with bounded exponential backoff between attempts;
        give up (loudly) after ``max_rollbacks``."""
        self._consecutive_skips = 0
        self.health.rollbacks += 1
        if self.health.rollbacks > self.cfg.max_rollbacks:
            raise RuntimeError(
                f"giving up after {self.cfg.max_rollbacks} rollbacks: "
                "training cannot make progress (persistent non-finite steps)")
        if not self.mgr or self.mgr.latest_step() is None:
            log("[trainer] consecutive non-finite steps but no checkpoint "
                "to roll back to; continuing")
            return
        delay = min(self.cfg.rollback_backoff
                    * (2 ** (self.health.rollbacks - 1)),
                    self.cfg.rollback_backoff_max)
        time.sleep(delay)
        self.health.retries += 1
        self.try_resume()
        log(f"[trainer] rolled back to step {self.step} after "
            f"{self.cfg.max_consecutive_skips} consecutive skipped steps "
            f"(backoff {delay*1e3:.0f} ms)")

    def throughput(self) -> dict:
        """steps/s + lookups/s from the step wall-time ring buffer — the
        same definition bench_kernels reports (trainer.throughput_stats) —
        plus the tier host-traffic stats when the pool is tiered."""
        return throughput_stats(
            self._step_times, self.cfg.lookups_per_step,
            tier_stats=self.tier.stats() if self.tier is not None else None)

    def _track_straggler(self, dt: float):
        self._step_times.append(dt)   # deque(maxlen=256): O(1) ring buffer
        if len(self._step_times) >= 16:
            med = float(np.median(self._step_times))
            if dt > self.cfg.straggler_factor * med:
                self.health.straggler_steps += 1
