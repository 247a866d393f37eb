"""The training loop: epochs, metrics, TensorBoard, checkpoints, resume.

Role parity with both reference drivers — the PyTorch epoch loop
(``imagenet_pytorch_horovod.py:415-441``: train → rank-0 log_row/TB scalars →
validate → rank-0 checkpoint) and the TF Estimator train/evaluate flow
(``resnet_main.py:282-307``) — rebuilt around the jitted sharded step:

- the hot loop is `shard_batch → step_fn` only; metrics come back as
  replicated scalars already reduced across chips inside XLA (the
  reference needed a separate hvd.allreduce Metric class for this);
- primary-process discipline (`jax.process_index()==0`) for logging,
  TensorBoard and throughput reporting, matching the reference's
  ``hvd.rank()==0`` gates;
- checkpoint each epoch + resume-from-latest via orbax (every host
  participates in sharded save/restore — no rank-0 special case);
- end-of-run summary: total images/sec over the train wall-clock
  (``_log_summary`` parity, ``resnet_main.py:184-200``).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from distributeddeeplearning_tpu.obs import goodput as goodput_mod
from distributeddeeplearning_tpu.obs.goodput import GoodputLedger
from distributeddeeplearning_tpu.obs.registry import get_registry
from distributeddeeplearning_tpu.obs.trace import get_tracer
from distributeddeeplearning_tpu.parallel.distributed import is_primary
from distributeddeeplearning_tpu.parallel.sharding import shard_batch
from distributeddeeplearning_tpu.train.checkpoint import Checkpointer
from distributeddeeplearning_tpu.train.resilience import (
    AnomalyDetector,
    AnomalyError,
    PreemptionError,
    PreemptionGuard,
    StepWatchdog,
)
from distributeddeeplearning_tpu.utils import faults as faults_mod
from distributeddeeplearning_tpu.utils.retry import RateLimitedLogger, retry_call
from distributeddeeplearning_tpu.utils.throughput import ExamplesPerSecondTracker

logger = logging.getLogger("ddlt.train")


def jnp_add(a, b):
    return a + b


# One jitted dispatch per step for the metric accumulation instead of one
# per metric: per-dispatch latency is material on remote backends, and this
# runs every hot-loop step.  Module-level so the compiled executable is
# shared across Trainer instances and epochs.
_acc_add = jax.jit(lambda a, b: jax.tree.map(jnp_add, a, b))

Batch = Dict[str, np.ndarray]


class MetricsLog:
    """Append-only JSONL of per-epoch metric rows (AML ``run.log_row`` role).

    Rank-0 only; best-effort — a failing log write must never kill training.
    Writes go through the bounded-backoff retry helper (``utils/retry.py``)
    so transient storage errors don't silently eat rows; a row dropped after
    exhausting retries is logged once a minute at most (rate-limited), with
    a running ``dropped_rows`` count.
    GCS objects are immutable, so the gs:// path keeps the accumulated rows
    in memory (seeded once from an existing file on resume) and rewrites the
    small object per append — one upload, no per-epoch re-read.
    """

    def __init__(self, path: Optional[str]):
        self.path = path if (path and is_primary()) else None
        self._buffer = ""
        self.dropped_rows = 0
        # At most one "rows are being dropped" line a minute: the log
        # stream that still works must not be flooded by the one that
        # doesn't.
        self._drop_warn = RateLimitedLogger(logger.warning, min_interval_s=60.0)
        if self.path is None:
            return
        if self.path.startswith("gs://"):
            try:
                import tensorflow as tf

                if tf.io.gfile.exists(self.path):  # resume: keep prior rows
                    with tf.io.gfile.GFile(self.path, "r") as f:
                        self._buffer = f.read()
            except Exception as exc:  # pragma: no cover
                logger.warning("metrics log init failed (%s): %s", self.path, exc)
        else:
            import os

            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)

    def _write(self, line: str) -> None:
        faults_mod.get_plan().maybe_io_error("metrics")
        if self.path.startswith("gs://"):
            import tensorflow as tf

            with tf.io.gfile.GFile(self.path, "w") as f:
                f.write(self._buffer + line)
            self._buffer += line  # only on success: a retry resends the row
        else:
            with open(self.path, "a") as f:
                f.write(line)

    def append(self, row: Dict[str, Any]) -> None:
        if self.path is None:
            return
        import json

        line = json.dumps(row) + "\n"
        try:
            retry_call(
                self._write, line,
                retries=3, base_delay=0.05, max_delay=2.0,
                description=f"metrics append ({self.path})",
            )
        except Exception as exc:  # environment-specific storage failures
            self.dropped_rows += 1
            self._drop_warn(
                "metrics row dropped after retries (%s rows dropped so far, "
                "path %s): %s", self.dropped_rows, self.path, exc,
            )


class TensorBoardLogger:
    """Rank-0 TensorBoard scalar writer (tensorboardX parity,
    ``imagenet_pytorch_horovod.py:325-329,426-436``), via tf.summary."""

    def __init__(self, logdir: Optional[str]):
        self._writer = None
        if logdir and is_primary():
            import tensorflow as tf

            self._writer = tf.summary.create_file_writer(logdir)

    def scalars(self, tag_prefix: str, values: Dict[str, float], step: int) -> None:
        if self._writer is None:
            return
        import tensorflow as tf

        with self._writer.as_default():
            for name, value in values.items():
                tf.summary.scalar(f"{tag_prefix}/{name}", value, step=step)

    def flush(self) -> None:
        if self._writer is not None:
            self._writer.flush()


@dataclasses.dataclass
class TrainerConfig:
    epochs: int = 90
    steps_per_epoch: int = 0  # required: total_batches // world (resnet_main.py:246)
    eval_steps: Optional[int] = None  # None = drain the eval iterator
    global_batch_size: int = 0
    log_every: int = 100  # ExamplesPerSecondHook cadence (utils.py:23)
    checkpoint_dir: Optional[str] = None
    # Save inside the step loop every N true steps (in addition to the
    # epoch-end save).  At pod scale an epoch is ~1,250 steps; without this
    # a preemption re-does up to a full epoch.  Resume lands on the EXACT
    # step (see fit's step-indexed factory for replay-free data resume).
    checkpoint_every_steps: Optional[int] = None
    tensorboard_dir: Optional[str] = None
    resume: bool = True
    max_to_keep: int = 5
    # jax.profiler trace of a step window (primary process only): steps
    # [profile_start, profile_start + profile_steps) of the first epoch run.
    profile_dir: Optional[str] = None
    profile_start: int = 10  # skip compile + warmup steps
    profile_steps: int = 10
    # Per-epoch metric rows appended as JSONL (primary process only) — the
    # reference's AML run.log_row channel (imagenet_pytorch_horovod.py:424-435).
    # Local paths and gs:// both work (gs via tf.io.gfile when available).
    metrics_path: Optional[str] = None
    # Host->device input staging depth: a background thread decodes and
    # device_puts the next N train batches while the device executes the
    # current one (utils/prefetch.py).  0 disables (synchronous fetch).
    prefetch: int = 2
    # Multi-host eval buffers the local eval split in host RAM to agree on a
    # common batch count with ONE allgather (see Trainer.evaluate); this caps
    # how many batches may be buffered.  The default comfortably covers
    # ImageNet-val-sized eval splits; raise it deliberately for bigger eval
    # sets (or set eval_steps, which bounds the drain outright).
    eval_buffer_batches: int = 4096
    # ---- resilience knobs (train/resilience.py) ------------------------
    # Preemption guard: SIGTERM/SIGINT set a flag the hot loop checks each
    # step; on the next boundary a SYNCHRONOUS emergency checkpoint is
    # written and PreemptionError raised (exit code 75 — EX_TEMPFAIL —
    # under the workload runner, the signal a supervisor restarts on).
    # None = auto: enabled exactly when a checkpoint_dir is configured.
    preemption_guard: Optional[bool] = None
    # Preemption GRACE WINDOW (seconds from SIGTERM to the platform's
    # SIGKILL).  When set, the emergency-checkpoint path plumbs the
    # window's remainder into the storage retry layer as a hard deadline
    # (retry_call(deadline_s=...)) so backoff can never sleep past the
    # kill — a checkpoint that retries itself into the SIGKILL saves
    # nothing.  None = unknown window, retries stay wall-clock-unbounded.
    preemption_grace_s: Optional[float] = None
    # Host-side anomaly detection: abort (AnomalyError) after this many
    # CONSECUTIVE non-finite loss/grad-norm steps; isolated blips are
    # counted and tolerated.  None = off.  Costs one device sync per step;
    # pair it with build_train_step(skip_nonfinite=True) so the anomalous
    # update is also DISCARDED on device (otherwise detection sees the NaN
    # only after it has already poisoned the params).
    anomaly_max_consecutive: Optional[int] = None
    # On AnomalyError, restore the last checkpoint and keep training (at
    # most anomaly_max_rollbacks times per fit) instead of propagating.
    # Requires a checkpointer with at least one saved step and resume=True;
    # with a plain-iterator data stream the rollback replays from wherever
    # the stream happens to be (the step-indexed factory form is exact).
    anomaly_rollback: bool = False
    anomaly_max_rollbacks: int = 1
    # Hot-loop watchdog: if the gap between completed steps exceeds this
    # many seconds, dump all-thread stacks to stderr and hard-exit 70 (the
    # hung-collective killer on multi-host meshes — one dead host blocks
    # every other host INSIDE an XLA collective with no exception).  Arms
    # after the first step of each epoch (compile excluded) and disarms
    # across eval/checkpoint phases.  None = off.
    step_deadline_s: Optional[float] = None
    # ---- observability (obs/) ------------------------------------------
    # Append a metrics-registry snapshot (counters/gauges/histograms as
    # one JSONL row) here at every epoch boundary, primary process only.
    # Writes go through the retry layer + DDLT_FAULTS io_error hook, same
    # as the metrics log; append-only, so rows survive restarts.
    obs_metrics_path: Optional[str] = None
    # Goodput ledger (obs/goodput.py): classify 100% of the fit's wall
    # into named categories (productive/redone steps, compile, data
    # wait, checkpoint blocking, eval, recovery, other) and append one
    # restart-durable JSONL segment per fit incarnation here — the
    # stitched file is the GOODPUT artifact's evidence.  None (the
    # default) = disabled: the hot-loop mark calls reduce to one
    # attribute check (lint-pinned zero-sync either way).
    goodput_path: Optional[str] = None


def _drain_bounded(batches: Iterator, limit, cap: int) -> list:
    """Buffer up to ``limit`` batches, refusing to exceed ``cap`` — the
    multi-host eval drain's RAM guard (an eval split larger than expected
    must fail loudly, not swap the host)."""
    local: list = []
    for batch in batches:
        local.append(batch)
        if limit is not None and len(local) >= limit:
            break
        if len(local) > cap:
            raise RuntimeError(
                f"multi-host eval buffered more than eval_buffer_batches="
                f"{cap} batches on this host; set TrainerConfig.eval_steps "
                "to bound the eval pass, or raise eval_buffer_batches if "
                "the host has RAM for a larger eval split"
            )
    return local


@dataclasses.dataclass
class FitResult:
    epochs_run: int
    final_train_metrics: Dict[str, float]
    final_eval_metrics: Optional[Dict[str, float]]
    total_images: int
    train_wall_seconds: float
    # resilience accounting: non-finite steps whose update was skipped, and
    # checkpoint rollbacks taken by the anomaly handler during this fit
    anomalous_steps: int = 0
    rollbacks: int = 0

    @property
    def images_per_second(self) -> float:
        return self.total_images / max(self.train_wall_seconds, 1e-9)


class Trainer:
    # class-level fallback so a partially-constructed Trainer (tests
    # drive isolated paths via ``Trainer.__new__``) still has inert
    # ledger marks; __init__ always overrides with the configured one
    goodput = GoodputLedger(enabled=False)
    _flops_probed = True

    def __init__(
        self,
        mesh,
        train_step: Callable,
        *,
        eval_step: Optional[Callable] = None,
        config: TrainerConfig,
    ):
        if config.steps_per_epoch <= 0:
            raise ValueError("steps_per_epoch must be positive")
        self.mesh = mesh
        self.train_step = train_step
        self.eval_step = eval_step
        self.config = config
        self.tb = TensorBoardLogger(config.tensorboard_dir)
        self.metrics_log = MetricsLog(config.metrics_path)
        self.checkpointer = (
            Checkpointer(config.checkpoint_dir, max_to_keep=config.max_to_keep)
            if config.checkpoint_dir
            else None
        )
        # wall-clock goodput accounting (no-op marks unless goodput_path
        # is set); one ledger per Trainer, one SEGMENT per fit attempt
        self.goodput = GoodputLedger(config.goodput_path)
        self._flops_probed = False

    def fit(
        self,
        state,
        train_batches,
        eval_batches_factory: Optional[Callable[[], Iterator[Batch]]] = None,
    ) -> tuple:
        """Run the epoch loop; returns (final_state, FitResult).

        ``train_batches`` is either a batch iterator or a STEP-INDEXED
        factory ``f(start_step) -> Iterator`` (its first yield is the batch
        for true step ``start_step``).  The factory form is what makes
        mid-epoch resume exact: after restoring step k the factory is asked
        for the stream starting at k, so no batch repeats and no batch is
        skipped — replay-free for indexable pipelines (synthetic, raw
        cache).  A plain iterator resumes wherever the stream happens to be
        (the r03 behavior): correct for IID-shuffled repeat streams, but
        not bit-reproducible against an uninterrupted run.

        Resilience wiring (all opt-in via TrainerConfig; see
        ``train/resilience.py``): a PreemptionGuard converting SIGTERM into
        emergency-checkpoint + PreemptionError, an AnomalyDetector over
        per-step loss/grad-norm with optional rollback-to-last-checkpoint,
        a StepWatchdog deadline on hot-loop progress, and the
        ``DDLT_FAULTS`` injection hooks that exercise all of it in tests.
        """
        cfg = self.config
        plan = faults_mod.get_plan()
        factory = (
            train_batches
            if callable(train_batches) and not hasattr(train_batches, "__next__")
            else None
        )
        stream = None if factory is not None else train_batches

        use_guard = cfg.preemption_guard
        if use_guard is None:
            use_guard = self.checkpointer is not None
        guard = (
            PreemptionGuard(grace_s=cfg.preemption_grace_s).install()
            if use_guard
            else None
        )
        if plan and guard is None and any(
            s.kind == "preempt" for s in plan.specs
        ):
            logger.warning(
                "DDLT_FAULTS contains a preempt fault but the preemption "
                "guard is disabled (no checkpoint_dir?) — it will not fire"
            )
        detector = (
            AnomalyDetector(cfg.anomaly_max_consecutive)
            if cfg.anomaly_max_consecutive
            else None
        )
        watchdog = (
            StepWatchdog(cfg.step_deadline_s).start()
            if cfg.step_deadline_s
            else None
        )

        rollbacks = 0
        # HBM attribution (obs/ledger.py): the train state's leaves go on
        # the process ledger by semantic owner — params vs optimizer
        # state vs batch stats — read through ``self._obs_state`` (the
        # hot loop re-points it at the live state each step, so the
        # providers always see the CURRENT buffers, never a donated
        # generation).  Registered once per Trainer; the ledger holds the
        # Trainer weakly, so dropping the Trainer drops the accounting.
        self._obs_state = state
        self._register_hbm_owners()
        # the ledger becomes the PROCESS ledger for the fit so deep
        # layers (Checkpointer save/wait joins) can attach their detail
        # notes without plumbing; restored in the outer finally
        prev_ledger = (
            goodput_mod.set_ledger(self.goodput)
            if self.goodput.enabled else None
        )
        try:
            while True:
                # one ledger segment per fit attempt: begin() re-reads
                # prior segments so redone-step classification survives
                # both in-process rollbacks and cross-process restarts
                self.goodput.begin()
                start_epoch = 0
                start_step_in_epoch = 0
                restored_step = None
                if self.checkpointer is not None and cfg.resume:
                    state, restored_step = self.checkpointer.restore(state)
                    if restored_step is None:
                        # resumed nothing: a NEW run lineage — a reused
                        # ledger file's earlier segments must not mark
                        # this run's steps redone (obs/goodput.py)
                        self.goodput.fresh_start()
                    if restored_step is not None:
                        self.goodput.set_resumed_step(int(restored_step))
                        start_epoch = int(restored_step) // cfg.steps_per_epoch
                        start_step_in_epoch = (
                            int(restored_step) % cfg.steps_per_epoch
                        )
                        if is_primary():
                            logger.info(
                                "resuming from step %d (epoch %d, step %d "
                                "within it)",
                                restored_step, start_epoch,
                                start_step_in_epoch,
                            )
                else:
                    # no checkpointer / resume disabled: by construction
                    # nothing was resumed — new run lineage
                    self.goodput.fresh_start()
                batches = (
                    factory(int(restored_step or 0))
                    if factory is not None
                    else stream
                )
                if plan:
                    batches = plan.wrap_data(
                        batches, start_step=int(restored_step or 0)
                    )

                owned_prefetch = None
                if cfg.prefetch > 0:
                    from distributeddeeplearning_tpu.utils.prefetch import (
                        prefetch_to_device,
                    )

                    batches = owned_prefetch = prefetch_to_device(
                        batches, self.mesh, size=cfg.prefetch
                    )

                attempt_reason = "completed"
                try:
                    state, result = self._fit_inner(
                        state, batches, eval_batches_factory, start_epoch,
                        start_step_in_epoch, guard=guard, detector=detector,
                        watchdog=watchdog, plan=plan,
                    )
                    result.rollbacks = rollbacks
                    return state, result
                except AnomalyError as exc:
                    # the finally below cannot see a HANDLED exception
                    # (Python clears it once this block completes), so
                    # the rolled-back attempt's segment reason is stamped
                    # here, not from sys.exc_info()
                    attempt_reason = type(exc).__name__
                    if watchdog is not None:
                        # the rollback restore below is storage-bound, not
                        # hot-loop progress
                        watchdog.pause()
                    # The live (finite, thanks to the in-jit guard) state is
                    # the restore template for the rollback pass.
                    state = getattr(exc, "state", state)
                    # restore-eligibility is the VERIFIED step: rolling
                    # back into a corrupt generation would trade a
                    # diverging run for a bricked one
                    rollback_to = (
                        self.checkpointer.latest_verified_step()
                        if self.checkpointer is not None
                        else None
                    )
                    can_roll = (
                        cfg.anomaly_rollback
                        and cfg.resume
                        and rollback_to is not None
                        and rollbacks < cfg.anomaly_max_rollbacks
                    )
                    if not can_roll:
                        raise
                    rollbacks += 1
                    detector = AnomalyDetector(cfg.anomaly_max_consecutive)
                    get_tracer().event(
                        "resilience/rollback", cat="resilience",
                        step=exc.step,
                        to_step=rollback_to,
                    )
                    logger.warning(
                        "anomaly abort at step %s — rolling back to "
                        "checkpoint step %s (%d/%d rollbacks)",
                        exc.step, rollback_to,
                        rollbacks, cfg.anomaly_max_rollbacks,
                    )
                finally:
                    if owned_prefetch is not None:
                        # Stop the worker deterministically: without the
                        # close, the thread keeps decoding and device_put-ing
                        # past what fit consumed (and keeps running during
                        # error handling if the loop raised).
                        owned_prefetch.close()
                    if self.checkpointer is not None:
                        # Drain pending async saves even when the loop raised
                        # (data stream died, preemption signal, ...): the
                        # state snapshots were already copied to host, and
                        # finalizing them is the difference between resuming
                        # at the last checkpoint_every_steps boundary and
                        # losing it.
                        self.checkpointer.wait()
                        self.goodput.mark("checkpoint_blocking")
                    # close the attempt's ledger segment whatever happened
                    # — a PreemptionError unwinding here still appends its
                    # segment, which is what makes the ledger restart-
                    # durable (stitching charges the gap to recovery)
                    import sys as _sys

                    exc_type = _sys.exc_info()[0]
                    self.goodput.end(
                        reason=(
                            attempt_reason if exc_type is None
                            else exc_type.__name__
                        )
                    )
        finally:
            if watchdog is not None:
                watchdog.stop()
            if guard is not None:
                guard.uninstall()
            if prev_ledger is not None:
                goodput_mod.set_ledger(prev_ledger)

    def _maybe_measure_flops(self, state, batch) -> None:
        """Best-effort MFU numerator: XLA's own cost model for ONE train
        step (``utils/hardware.step_flops``), fed into the goodput
        ledger.  Only attempted when the ledger is on AND the chip has a
        known peak — off-TPU the MFU column is omitted anyway, so the
        AOT-lowering cost (a second trace) is never paid on the CPU test
        mesh.  The probe stops at ``.lower()`` — the UNOPTIMIZED cost
        analysis, which is what the model-FLOPs numerator wants anyway
        (PaLM MFU counts model FLOPs, not remat re-execution) — because
        ``.lower().compile()`` would run a SECOND full XLA compile that
        the jit dispatch cache never sees, doubling large-model startup.
        Any failure (a step builder without ``.lower``, a backend
        without a cost model) just leaves MFU omitted.
        """
        if self._flops_probed or not self.goodput.enabled:
            return
        self._flops_probed = True
        try:
            from distributeddeeplearning_tpu.utils.hardware import (
                peak_bf16_flops,
                step_flops,
            )

            if peak_bf16_flops() is None:
                return
            lowered = self.train_step.lower(state, batch)
            self.goodput.set_flops_per_step(step_flops(lowered))
        except Exception:  # MFU is an optional column, never a crash
            pass

    def _register_hbm_owners(self) -> None:
        """Register the train state's leaves on the process HBM ledger
        (obs/ledger.py) by semantic owner.  Idempotent per Trainer; the
        providers read ``self._obs_state``, which the hot loop re-points
        at the live state every step."""
        if getattr(self, "_hbm_registered", False):
            return
        self._hbm_registered = True
        from distributeddeeplearning_tpu.obs.ledger import get_ledger

        ledger = get_ledger()
        def _of_state(attr):
            def provider(trainer):
                return getattr(
                    getattr(trainer, "_obs_state", None), attr, None
                )
            return provider

        ledger.register("params", self, _of_state("params"))
        ledger.register("opt_state", self, _of_state("opt_state"))
        ledger.register("batch_stats", self, _of_state("batch_stats"))

    def _emergency_stop(self, step: int, state, watchdog, guard=None) -> None:
        """Preemption noticed at a step boundary: synchronous emergency
        checkpoint, then PreemptionError (→ exit 75 under the runner)."""
        if watchdog is not None:
            watchdog.pause()
        get_tracer().event(
            "resilience/preempted", cat="resilience", step=step
        )
        if self.checkpointer is not None:
            logger.warning(
                "preemption at step %d — writing emergency checkpoint", step
            )
            # save() copies device→host synchronously; wait() drains the
            # background write.  Both must land BEFORE the resumable exit:
            # the grace window is short and the checkpoint IS the recovery
            # — so the window's REMAINDER (re-read before each phase; save
            # may have consumed most of it) deadline-bounds the retry
            # backoff inside both (retry_call(deadline_s=...)).
            self.goodput.mark("other")
            with get_tracer().span(
                "train/emergency_checkpoint", cat="resilience", step=step
            ):
                self.checkpointer.save(
                    step, state,
                    deadline_s=(
                        guard.remaining_grace() if guard is not None else None
                    ),
                )
                self.checkpointer.wait(
                    deadline_s=(
                        guard.remaining_grace() if guard is not None else None
                    ),
                )
            self.goodput.mark("checkpoint_blocking")
            logger.warning("emergency checkpoint at step %d complete", step)
        raise PreemptionError(
            f"preempted at step {step} (emergency checkpoint "
            f"{'written' if self.checkpointer is not None else 'UNAVAILABLE'})",
            step=step,
        )

    def _fit_inner(
        self, state, train_batches, eval_batches_factory, start_epoch,
        start_step_in_epoch=0, *, guard=None, detector=None, watchdog=None,
        plan=None,
    ) -> tuple:
        cfg = self.config
        # one tracer for the whole fit: train-side spans (data wait / step
        # / checkpoint) land on the same timeline as serve and resilience
        # events.  Disabled (the default) = shared no-op spans, no clock
        # reads — the hot-loop lint pins the loop body sync-free either way.
        trace = get_tracer()
        # everything since the segment's begin() — checkpoint restore,
        # stream construction, prefetch spin-up — is restart/recovery
        # work, not training
        self.goodput.mark("recovery")
        tracker = ExamplesPerSecondTracker(
            global_batch_size=cfg.global_batch_size,
            every_n_steps=cfg.log_every,
            report=logger.info if is_primary() else (lambda *_: None),
        )
        tracker.begin()
        train_t0 = time.monotonic()
        total_images = 0
        train_metrics: Dict[str, float] = {}
        eval_metrics: Optional[Dict[str, float]] = None
        epoch = start_epoch
        profile_active = False
        profile_pending = cfg.profile_dir is not None and is_primary()
        total_steps = (
            (cfg.epochs - start_epoch) * cfg.steps_per_epoch
            - start_step_in_epoch
        )
        profile_start = cfg.profile_start
        if profile_pending and total_steps <= cfg.profile_start:
            logger.warning(
                "profile_dir set but the run has only %d steps (< profile_start"
                " %d) — starting the trace at step 0 instead",
                total_steps, cfg.profile_start,
            )
            profile_start = 0
        global_step = 0
        anomalous_total = 0

        for epoch in range(start_epoch, cfg.epochs):
            # Metrics accumulate ON DEVICE (one tiny async add per step);
            # the host only blocks every log_every steps and at epoch end.
            # A per-step float() sync would serialize dispatch and was the
            # gap between Trainer.fit and the benchmark harness throughput.
            acc = None
            epoch_t0 = time.monotonic()
            first_step = start_step_in_epoch if epoch == start_epoch else 0
            steps_this_epoch = cfg.steps_per_epoch - first_step
            anomalous_this_epoch = 0
            for step_i in range(first_step, cfg.steps_per_epoch):
                true_step = epoch * cfg.steps_per_epoch + step_i + 1
                if profile_pending and global_step >= profile_start:
                    jax.profiler.start_trace(cfg.profile_dir)
                    profile_active, profile_pending = True, False
                with trace.span("train/data_wait", step=true_step):
                    host_batch = next(train_batches)
                self.goodput.mark("data_wait")
                if plan:
                    host_batch = plan.poison_batch(true_step, host_batch)
                with trace.span("train/step", step=true_step):
                    batch = shard_batch(self.mesh, host_batch)
                    if global_step == 0:
                        # MFU numerator (no-op off-TPU / ledger-disabled)
                        self._maybe_measure_flops(state, batch)
                    state, metrics = self.train_step(state, batch)
                # re-point the HBM-ledger providers at the LIVE state
                # (the previous generation's buffers were just donated);
                # one attribute store — no sync, no walk
                self._obs_state = state
                anomalous = False
                if detector is not None:
                    # One host sync per step — the price of reacting to a
                    # diverging run before it wastes the rest of the epoch.
                    # (sync-ok markers: the analysis/host_sync.py checker
                    # waives exactly these lines against the trainer
                    # region's sync_budget in analysis/regions.py; any NEW
                    # per-step host sync — or a stale marker — fails
                    # `ddlt lint` and tier-1.)
                    loss_v = float(metrics["loss"])  # sync-ok: anomaly detector
                    gn = metrics.get("grad_norm")
                    flagged = metrics.get("anomalous")
                    try:
                        anomalous = detector.observe(
                            true_step, loss_v,
                            float(gn) if gn is not None else None,  # sync-ok: anomaly detector
                            flagged=(
                                bool(float(flagged))  # sync-ok: anomaly detector
                                if flagged is not None else None
                            ),
                        )
                    except AnomalyError as exc:
                        exc.state = state  # restore template for rollback
                        raise
                if anomalous:
                    # NaN metrics must not poison the epoch accumulator
                    # (the on-device update was already skipped when the
                    # step was built with skip_nonfinite=True).
                    anomalous_this_epoch += 1
                    anomalous_total += 1
                else:
                    acc = metrics if acc is None else _acc_add(acc, metrics)
                if (step_i + 1) % cfg.log_every == 0:
                    jax.block_until_ready(acc)
                # charge the step's wall (dispatch + the detector/log-
                # boundary syncs above) to compile / step_redone /
                # step_productive — the ledger classifies (obs/goodput.py)
                self.goodput.mark_step(true_step)
                tracker.after_step()
                if watchdog is not None:
                    watchdog.tick(true_step)
                total_images += cfg.global_batch_size
                global_step += 1
                if profile_active and global_step >= (
                    profile_start + cfg.profile_steps
                ):
                    jax.block_until_ready(acc)
                    jax.profiler.stop_trace()
                    profile_active = False
                    logger.info("profiler trace written to %s", cfg.profile_dir)
                if (
                    self.checkpointer is not None
                    and cfg.checkpoint_every_steps
                    and true_step % cfg.checkpoint_every_steps == 0
                ):
                    if watchdog is not None:
                        # storage-bound phase: save() can block on the
                        # previous in-flight async write (plus its retry
                        # backoff) — not hot-loop hang evidence.  The next
                        # step's tick re-arms.
                        watchdog.pause()
                    # save() copies device→host synchronously, so the next
                    # step's donation cannot clobber the saved buffers; the
                    # serialize/write happens on orbax's background thread.
                    with trace.span("train/checkpoint", step=true_step):
                        self.checkpointer.save(true_step, state)
                    self.goodput.mark("checkpoint_blocking")
                if guard is not None:
                    if plan:
                        plan.maybe_preempt(true_step, guard)
                    if guard.preempted():
                        self._emergency_stop(
                            true_step, state, watchdog, guard=guard
                        )
            if profile_active:
                # Run shorter than the window: close the trace on step work
                # only — eval/checkpoint/TB below must not pollute it.
                jax.block_until_ready(acc)
                jax.profiler.stop_trace()
                profile_active = False
                logger.info("profiler trace written to %s", cfg.profile_dir)
            if watchdog is not None:
                # Eval, TB, checkpoints below have unbounded (storage-
                # dependent) duration; the deadline re-arms at the next
                # epoch's first completed step.
                watchdog.pause()
            counted_steps = steps_this_epoch - anomalous_this_epoch
            train_metrics = (
                {k: float(v) / counted_steps for k, v in acc.items()}
                if acc is not None and counted_steps > 0
                else {}
            )
            if anomalous_this_epoch:
                train_metrics["anomalous_steps"] = float(anomalous_this_epoch)
            # train-phase wall of THIS epoch (the float() above synced):
            # excludes the eval/checkpoint below, so per-epoch throughput
            # rows are comparable across epochs.
            epoch_train_wall = time.monotonic() - epoch_t0
            if is_primary():
                logger.info(
                    "epoch %d/%d: %s",
                    epoch + 1,
                    cfg.epochs,
                    {k: round(v, 4) for k, v in train_metrics.items()},
                )
            self.tb.scalars("train", train_metrics, epoch)
            # epoch rollup so far (metric readback, logs, TB) is loop
            # bookkeeping, not training
            self.goodput.mark("other")

            if self.eval_step is not None and eval_batches_factory is not None:
                with trace.span("train/eval", epoch=epoch + 1):
                    eval_metrics = self.evaluate(
                        state, eval_batches_factory()
                    )
                self.goodput.mark("eval")
                if is_primary():
                    logger.info(
                        "epoch %d validation: %s",
                        epoch + 1,
                        {k: round(v, 4) for k, v in eval_metrics.items()},
                    )
                self.tb.scalars("val", eval_metrics, epoch)

            # run.log_row parity: one row per epoch with both metric sets
            row: Dict[str, Any] = {"epoch": epoch + 1}
            row.update({f"train_{k}": v for k, v in train_metrics.items()})
            if eval_metrics:
                row.update({f"val_{k}": v for k, v in eval_metrics.items()})
            row["images_per_second"] = (
                steps_this_epoch * cfg.global_batch_size
            ) / max(epoch_train_wall, 1e-9)
            if epoch == start_epoch:
                # The first epoch's wall includes train_step JIT compilation
                # (~20-40s on TPU); flag the row so nobody diffs it against
                # later epochs or the benchmark harness numbers.
                row["includes_compile"] = True
            self.metrics_log.append(row)

            # per-epoch rollup into the obs registry (never per step): the
            # same counters/gauges the serve path feeds, one process view
            reg = get_registry()
            reg.counter("train.steps").inc(steps_this_epoch)
            reg.counter("train.epochs").inc()
            if anomalous_this_epoch:
                reg.counter("train.anomalous_steps").inc(
                    anomalous_this_epoch
                )
            reg.gauge("train.images_per_second").set(
                row["images_per_second"]
            )
            if "loss" in train_metrics:
                reg.gauge("train.loss").set(train_metrics["loss"])
            reg.histogram("train.epoch_train_wall_s").record(
                epoch_train_wall
            )
            if cfg.obs_metrics_path and is_primary():
                reg.write_snapshot(cfg.obs_metrics_path, epoch=epoch + 1)

            if self.checkpointer is not None:
                self.goodput.mark("other")
                with trace.span(
                    "train/checkpoint", step=(epoch + 1) * cfg.steps_per_epoch
                ):
                    self.checkpointer.save(
                        (epoch + 1) * cfg.steps_per_epoch, state
                    )
                self.goodput.mark("checkpoint_blocking")

        wall = time.monotonic() - train_t0
        self.tb.flush()
        if self.checkpointer is not None:
            self.checkpointer.wait()
        result = FitResult(
            epochs_run=max(cfg.epochs - start_epoch, 0),
            final_train_metrics=train_metrics,
            final_eval_metrics=eval_metrics,
            total_images=total_images,
            train_wall_seconds=wall,
            anomalous_steps=anomalous_total,
        )
        if is_primary() and total_images:
            # _log_summary parity (resnet_main.py:184-200)
            logger.info("total images/sec: %.2f", result.images_per_second)
            logger.info("batch size: %d (global)", cfg.global_batch_size)
        return state, result

    def evaluate(self, state, eval_batches: Iterator[Batch]) -> Dict[str, float]:
        """Weighted-average eval metrics over a host-synchronized batch count.

        Per-host eval file shards can yield uneven batch counts; a host with
        extra batches would enter the eval-step collectives alone and hang
        the pod.  Hosts therefore agree ONCE per eval pass on a common batch
        count — each host counts its available batches up front (buffering
        them), the pod takes the minimum, and every host runs exactly that
        many steps with no further host round-trips.  Batches are weighted by
        size so ragged final batches do not bias top-1.
        """
        multi_host = jax.process_count() > 1
        limit = self.config.eval_steps
        if multi_host:
            from jax.experimental import multihost_utils

            # Drain (up to eval_steps) locally first: eval epochs are small
            # (ImageNet val = 50k images / pod) so buffering batch dicts of
            # host numpy arrays is cheap, and it turns N allgathers into 1.
            # The eval_buffer_batches cap keeps an unexpectedly large eval
            # split from silently eating host RAM — fail loudly instead.
            local = _drain_bounded(
                eval_batches, limit, self.config.eval_buffer_batches
            )
            common = int(
                multihost_utils.process_allgather(
                    np.asarray(len(local))
                ).min()
            )
            batches: Iterator[Batch] = iter(local[:common])
            limit = common
        else:
            batches = eval_batches
        # Size-weighted sums accumulate ON DEVICE (batch sizes are known on
        # the host, so the weights add no sync); the only host fetch is the
        # final per-metric float.  A per-batch float(v) here serialized
        # dispatch — a host round-trip per batch — the same bug the
        # train loop's on-device accumulator fixed (r02).
        sums: Dict[str, jax.Array] = {}
        total_weight = 0
        steps = 0
        while True:
            if limit is not None and steps >= limit:
                break
            batch = next(batches, None)
            if batch is None:
                break
            batch_size = len(next(iter(batch.values())))
            metrics = self.eval_step(state, shard_batch(self.mesh, batch))
            for k, v in metrics.items():
                weighted = v * batch_size
                sums[k] = weighted if k not in sums else sums[k] + weighted
            total_weight += batch_size
            steps += 1
        if not sums or total_weight == 0:
            # zero batches OR only zero-length batches (empty host shards):
            # the old AverageMeter.avg returned 0.0 here; an empty dict is
            # the cleaner "no eval happened" signal callers already handle
            return {}
        return {k: float(v) / total_weight for k, v in sums.items()}
