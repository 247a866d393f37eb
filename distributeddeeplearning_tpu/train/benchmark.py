"""Synthetic throughput benchmark harness.

Parity with ``PyTorch_benchmark/src/pytorch_synthetic_benchmark.py:51-126``:
N warmup batches, then ``num_iters`` timed iterations of ``num_batches_per_iter``
steps each; report img/sec mean ± 1.96σ per chip and total = world × mean.
Differences are TPU-native, not cosmetic:

- the timed unit is a **jitted train step over the mesh** — the gradient
  all-reduce rides ICI inside the XLA program, so "img/sec" includes the
  collective exactly as the reference's timed ``optimizer.step()`` includes
  the NCCL allreduce;
- each timing window is bounded by a device-to-host fetch of a step's loss
  scalar (JAX dispatch is async; a data-dependent fetch is a sync that
  holds on every PJRT backend) — and the fetch for window *i* happens only
  after window *i+1*'s steps are already dispatched, so the device never
  drains between windows and the D2H round-trip latency cancels out of the
  window-to-window deltas.  This is exactly the overlap a real training
  loop gets from reading metrics one step behind the computation;
- one fixed device-resident batch, donated state — steady-state HBM traffic
  only.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable, Dict, List, Optional

import jax

from distributeddeeplearning_tpu.parallel.mesh import world_size


@dataclasses.dataclass
class BenchmarkResult:
    model: str
    batch_size_per_chip: int
    num_devices: int
    img_sec_per_chip_mean: float
    img_sec_per_chip_ci95: float
    img_sec_total: float
    iter_times_s: List[float]

    def summary_lines(self) -> List[str]:
        # Report shape parity: pytorch_synthetic_benchmark.py:119-126
        return [
            f"Model: {self.model}",
            f"Batch size: {self.batch_size_per_chip} per chip",
            f"Number of chips: {self.num_devices}",
            f"Img/sec per chip: {self.img_sec_per_chip_mean:.1f} "
            f"+-{self.img_sec_per_chip_ci95:.1f}",
            f"Total img/sec on {self.num_devices} chip(s): "
            f"{self.img_sec_total:.1f} "
            f"+-{self.img_sec_per_chip_ci95 * self.num_devices:.1f}",
        ]


def _windowed_benchmark(
    step_fn: Callable,
    state,
    next_batch: Callable[[], object],
    *,
    model_name: str,
    batch_size_per_chip: int,
    num_devices: int,
    num_warmup_batches: int,
    num_iters: int,
    num_batches_per_iter: int,
    log: Optional[Callable[[str], None]],
    label: str,
) -> BenchmarkResult:
    """Shared warmup + overlapped-window timing core.

    Overlapped windows: dispatch window i+1 BEFORE fetching window i's
    sync scalar.  t[i] = host time window i's last step was observed
    complete; successive deltas subtract the (constant) D2H latency away
    and the device stream never drains, so the deltas measure pure device
    throughput — the number a jax.profiler trace reports.
    """
    global_batch = batch_size_per_chip * num_devices

    if log:
        log(f"Running {label}warmup ({num_warmup_batches} batches)...")
    metrics = None
    for _ in range(num_warmup_batches):
        state, metrics = step_fn(state, next_batch())
    if metrics is not None:
        float(metrics["loss"])  # force the dispatched chain to completion

    if log:
        log(
            f"Running {label}benchmark ({num_iters} iters x "
            f"{num_batches_per_iter} batches)..."
        )
    img_secs: List[float] = []
    iter_times: List[float] = []
    # num_iters + 1 windows are dispatched; the FIRST is an unmeasured
    # priming window — the warmup's blocking fetch drained the device, so
    # window 0 uniquely pays the pipeline-refill RTT before the device
    # resumes.  Timestamps start at window 0's fetch-completion; every
    # delta after that is pure device throughput.
    t_prev = None
    pending = None  # window i-1's metrics, fetched after window i dispatches
    for _ in range(num_iters + 1):
        for _ in range(num_batches_per_iter):
            state, metrics = step_fn(state, next_batch())
        if pending is not None:
            float(pending["loss"])
            now = time.perf_counter()
            if t_prev is not None:
                dt = now - t_prev
                iter_times.append(dt)
                img_secs.append(
                    global_batch * num_batches_per_iter / dt / num_devices
                )
            t_prev = now
        pending = metrics
    float(pending["loss"])  # last window drains with nothing queued behind
    dt = time.perf_counter() - t_prev
    iter_times.append(dt)
    img_secs.append(global_batch * num_batches_per_iter / dt / num_devices)

    mean = statistics.fmean(img_secs)
    stdev = statistics.stdev(img_secs) if len(img_secs) > 1 else 0.0
    result = BenchmarkResult(
        model=model_name,
        batch_size_per_chip=batch_size_per_chip,
        num_devices=num_devices,
        img_sec_per_chip_mean=mean,
        img_sec_per_chip_ci95=1.96 * stdev,
        img_sec_total=mean * num_devices,
        iter_times_s=iter_times,
    )
    if log:
        for line in result.summary_lines():
            log(line)
    return result


def run_benchmark(
    step_fn: Callable,
    state,
    batch,
    *,
    model_name: str = "model",
    batch_size_per_chip: int = 64,
    num_devices: Optional[int] = None,
    num_warmup_batches: int = 10,
    num_iters: int = 10,
    num_batches_per_iter: int = 10,
    log: Optional[Callable[[str], None]] = None,
) -> BenchmarkResult:
    """Benchmark ``step_fn(state, batch) -> (state, metrics)``.

    ``batch`` must already be placed on the mesh (global batch). Timings per
    iteration are global-batch steps; per-chip img/sec divides by the device
    count, matching the reference's per-GPU accounting
    (``pytorch_synthetic_benchmark.py:116-122``).
    """
    if num_devices is None:
        # derive from the batch's actual placement, not the global device
        # count — a step built over a subset mesh must not inflate img/sec
        leaves = jax.tree_util.tree_leaves(batch)
        if leaves and hasattr(leaves[0], "sharding"):
            num_devices = leaves[0].sharding.num_devices
        else:
            num_devices = world_size()
    return _windowed_benchmark(
        step_fn,
        state,
        lambda: batch,
        model_name=model_name,
        batch_size_per_chip=batch_size_per_chip,
        num_devices=num_devices,
        num_warmup_batches=num_warmup_batches,
        num_iters=num_iters,
        num_batches_per_iter=num_batches_per_iter,
        log=log,
        label="",
    )


def run_data_benchmark(
    step_fn: Callable,
    state,
    device_batches,
    *,
    model_name: str = "model",
    batch_size_per_chip: int = 64,
    num_devices: Optional[int] = None,
    num_warmup_batches: int = 10,
    num_iters: int = 10,
    num_batches_per_iter: int = 10,
    log: Optional[Callable[[str], None]] = None,
) -> BenchmarkResult:
    """Benchmark the step fed from a REAL input pipeline.

    Identical methodology to :func:`run_benchmark` except each step consumes
    the next batch from ``device_batches`` (an iterator of mesh-placed
    batches, e.g. ``utils.prefetch.prefetch_to_device`` over an input_fn) —
    so the number includes TFRecord read, JPEG decode, host→HBM transfer and
    any pipeline stalls, exactly the end-to-end rate a training run sees.
    The reference never isolates this (its input path is timed only inside
    full training runs); measuring it directly is how ``bench.py --data``
    produces its synthetic-vs-fed gap.

    Raises ``StopIteration`` if the pipeline runs dry before
    ``num_warmup_batches + (num_iters+1)*num_batches_per_iter`` batches
    (one extra unmeasured priming window); size the dataset (or use a
    repeating pipeline) accordingly.
    """
    if num_devices is None:
        num_devices = world_size()
    it = iter(device_batches)
    # Pipeline stalls show up in the window deltas (the next batch is
    # pulled before each dispatch) but the constant D2H fetch latency does
    # not — same methodology as the synthetic path, so the two rates
    # ``bench.py --data`` reports stay comparable.
    return _windowed_benchmark(
        step_fn,
        state,
        lambda: next(it),
        model_name=model_name,
        batch_size_per_chip=batch_size_per_chip,
        num_devices=num_devices,
        num_warmup_batches=num_warmup_batches,
        num_iters=num_iters,
        num_batches_per_iter=num_batches_per_iter,
        log=log,
        label="data-fed ",
    )
