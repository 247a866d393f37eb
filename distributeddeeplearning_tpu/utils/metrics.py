"""Training metrics: running averages, top-k accuracy, cross-replica reduction.

Parity targets in the reference:
- ``AverageMeter`` / ``accuracy`` (``PyTorch_imagenet/src/imagenet_pytorch_horovod.py:128-163``)
- allreduce-averaged ``Metric`` (``PyTorch_hvd/src/imagenet_pytorch_horovod.py:239-251``)

TPU-native design: accuracy and loss are computed *inside* the jitted step and
reduced with ``jax.lax.pmean`` over the mesh (no host-side allreduce); the
host-side meters here only aggregate already-reduced scalars over time.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


class AverageMeter:
    """Tracks current value, running sum, and average of a scalar stream.

    Superseded for new code by :mod:`..obs.registry` (``Gauge`` for
    last-value, ``Histogram`` for distributions — which also gives
    streaming p50/p90/p99); kept for the reference-parity call sites.
    """

    def __init__(self, name: str = ""):
        self.name = name
        self.reset()

    def reset(self) -> None:
        self.val = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val: float, n: int = 1) -> None:
        val = float(val)
        self.val = val
        self.sum += val * n
        self.count += n

    @property
    def avg(self) -> float:
        return self.sum / self.count if self.count else 0.0


def _total_order_key(x: jnp.ndarray) -> jnp.ndarray:
    """int32 keys that order as XLA's ``top_k`` and sort order float32:
    totally, with -0.0 below 0.0 and NaN beyond the infinities.  They are the
    sign-magnitude bits with the magnitude of the negatives flipped."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def label_in_topk(logits: jnp.ndarray, labels: jnp.ndarray, k: int) -> jnp.ndarray:
    """Whether each label is among the k largest of its ``logits`` row:
    ``logits`` [..., classes] against ``labels`` [...] gives bool [...].

    Membership is that of ``top_k`` (``jax.lax``) on the float32 logits, ties
    included (lowest index first), but nothing is sorted: the label is ranked by
    counting the classes ahead of it, one fused compare-and-reduce pass over
    the class axis whatever ``k`` is (``top_k`` lowers to a full sort of the
    row on the TPU: 83% of the LM train step at 50,000 classes).  The label's
    logit is picked by a masked max over the array that is counted, not by a
    gather: a sharded class axis partitions as a plain reduction, the max
    fuses into the loss's own max pass, and XLA cannot hand back another
    rounding of the logit (PERF.md, PR 27).  jit-safe (static k).
    """
    k = min(k, logits.shape[-1])  # top-5 on a <5-class head degrades gracefully
    x = logits.astype(jnp.float32)
    label = labels[..., None].astype(jnp.int32)
    index = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    at_label = _total_order_key(
        jnp.max(jnp.where(index == label, x, -jnp.inf), axis=-1, keepdims=True)
    )
    key = _total_order_key(x)
    ahead = (key > at_label) | ((key == at_label) & (index < label))
    return ahead.sum(axis=-1, dtype=jnp.int32) < k


def topk_correct(logits: jnp.ndarray, labels: jnp.ndarray, k: int) -> jnp.ndarray:
    """Number of examples whose true label is within the top-k logits.

    jit-safe (static k); used inside eval steps.
    """
    return jnp.sum(label_in_topk(logits, labels, k), dtype=jnp.float32)


def accuracy_topk(
    logits: jnp.ndarray, labels: jnp.ndarray, ks: Tuple[int, ...] = (1, 5)
) -> Dict[str, jnp.ndarray]:
    """Top-k accuracies as fractions in [0, 1] (reference reports percent)."""
    batch = logits.shape[0]
    return {f"top{k}": topk_correct(logits, labels, k) / batch for k in ks}


def pmean_metrics(metrics: Dict[str, jnp.ndarray], axis_name: str) -> Dict[str, jnp.ndarray]:
    """Cross-replica mean of a metrics dict, inside pmap/shard_map bodies.

    The XLA-collective replacement for the reference's host-side
    ``hvd.allreduce`` averaging ``Metric`` class.  The whole dict goes
    through ONE tree-level ``lax.pmean`` — a single psum primitive over all
    K leaves that XLA lowers to one fused collective — instead of K
    per-key reductions, so the metrics path adds one reduction per step no
    matter how many scalars a workload reports.
    """
    return jax.lax.pmean(dict(metrics), axis_name)


def confidence_interval_95(samples) -> Tuple[float, float]:
    """mean ± 1.96·σ of a sample list — the reference benchmark's reporting
    convention (``pytorch_synthetic_benchmark.py:119-122``)."""
    n = len(samples)
    if n == 0:
        return 0.0, 0.0
    mean = sum(samples) / n
    var = sum((s - mean) ** 2 for s in samples) / n
    return mean, 1.96 * math.sqrt(var)
