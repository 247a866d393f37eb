"""Post-training weight quantization of the transformer param pytree.

PTQ for serving: the matmul weights of a trained ``pipelined_transformer``
checkpoint (``blocks.{qkv,proj,w_in,w_out}`` and ``head``) become
:class:`~distributeddeeplearning_tpu.quant.qtensor.QTensor` leaves with
per-output-channel f32 scales; embeddings, position table and layer-norm
gains stay f32 (they are lookups/elementwise — no int8 matmul to win, and
they are the quantization-sensitive leaves every production int8 recipe
keeps high-precision).

Two scale observers:

- **absmax** — scale = max|w| per channel: exact range coverage, one
  outlier row can waste the grid;
- **percentile** — scale = P-th percentile of |w| per channel: clips the
  outlier tail (saturating those weights) so the 8-bit grid spends its
  codes on the bulk of the distribution.

``calibrate_params`` additionally runs a handful of calibration prompts
through the f32 AND quantized model and reports per-position logit MAE and
greedy-token agreement — the go/no-go numbers a deployment reads before
flipping traffic to the quantized path (``ddlt serve --quantize-weights
int8 --calib-prompts N`` prints them; ``bench.py --quant`` archives them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.quant.qtensor import QTensor, quantize

PyTree = Any

#: Block-stack matmul leaves that quantize (contraction dim at -2 after
#: the leading [L] stack dim — the negative-axis convention makes the
#: same QTensor metadata valid before and after the layer scan slices L).
BLOCK_MATMUL_LEAVES = ("qkv", "proj", "w_in", "w_out")


class AbsmaxObserver:
    """scale = max|w| per channel — the default, exact-range observer."""

    def __call__(self, x: jax.Array, axis: int) -> jax.Array:
        return jnp.max(jnp.abs(x), axis=axis, keepdims=True)


class PercentileObserver:
    """scale = P-th percentile of |w| per channel: outliers saturate,
    the bulk of the distribution gets the finer grid."""

    def __init__(self, percentile: float = 99.9):
        if not 0.0 < percentile <= 100.0:
            raise ValueError(f"percentile must be in (0, 100], got {percentile}")
        self.percentile = percentile

    def __call__(self, x: jax.Array, axis: int) -> jax.Array:
        return jnp.percentile(
            jnp.abs(x), self.percentile, axis=axis, keepdims=True
        )


def _make_observer(method: str, percentile: float):
    if method == "absmax":
        return AbsmaxObserver()
    if method == "percentile":
        return PercentileObserver(percentile)
    raise ValueError(f"unknown observer method {method!r}")


def quantize_params(
    params: PyTree,
    *,
    method: str = "absmax",
    percentile: float = 99.9,
    block: Optional[int] = None,
) -> PyTree:
    """Quantize the matmul weights of a ``pipelined_transformer`` params
    pytree to int8 QTensors (per-output-channel scales, ``axis=-2``);
    embed/pos/ln leaves pass through untouched.  Idempotent-safe: already-
    quantized leaves raise (re-quantizing int8 codes would double the
    error silently)."""
    observer = _make_observer(method, percentile)

    def q(w):
        if isinstance(w, QTensor):
            raise ValueError("params are already quantized")
        return quantize(w, axis=-2, block=block, observer=observer)

    out = dict(params)
    out["blocks"] = dict(params["blocks"])
    for name in BLOCK_MATMUL_LEAVES:
        out["blocks"][name] = q(params["blocks"][name])
    out["head"] = q(params["head"])
    return out


@jax.jit
def _round_to_bf16(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), tree)


def bf16_matmul_params(params: PyTree) -> PyTree:
    """The params pytree with its float32 matmul weights rounded to
    bfloat16: the leaves :func:`quantize_params` quantizes
    (``BLOCK_MATMUL_LEAVES`` of ``blocks``, and ``head``), each
    ``leaf.astype(bfloat16)``, in ONE jitted call over all of them.

    A TPU runs a default-precision float32 product as one bf16 pass with
    float32 accumulation and rounds the weight operand on every call; a
    tree rounded once here hands ``qmatmul`` the operand that pass reads
    (``serve/engine.py`` decides where that holds).  Everything else
    passes through as the very object it was: QTensor and bf16 leaves,
    ``embed``, ``pos``, the LayerNorm scales, the leaves of another
    model's tree (no ``blocks``).  Idempotent: a tree with no float32
    matmul leaf comes back itself."""

    def rounds(leaf):
        return not isinstance(leaf, QTensor) and leaf.dtype == jnp.float32

    blocks = params.get("blocks", {})
    stacks = {
        name: blocks[name] for name in BLOCK_MATMUL_LEAVES
        if name in blocks and rounds(blocks[name])
    }
    head = (
        {"head": params["head"]}
        if "head" in params and rounds(params["head"]) else {}
    )
    if not stacks and not head:
        return params
    stacks, head = _round_to_bf16((stacks, head))
    out = {**params, **head}
    if stacks:
        out["blocks"] = {**blocks, **stacks}
    return out


def abstract_quantized_params(params_abs: PyTree) -> PyTree:
    """ShapeDtypeStruct skeleton of :func:`quantize_params`' output with
    no quantization math run — ``jax.eval_shape`` over the PTQ transform.

    The static-analysis program audit (``analysis/program_audit.py``)
    traces the int8-weight serving programs on exactly this skeleton, so
    the audited QTensor layout (values int8, keepdims f32 scales at the
    negative-axis convention) can never drift from what ``quantize_params``
    actually produces."""
    return jax.eval_shape(quantize_params, params_abs)


def params_dtype(params: PyTree) -> str:
    """``"int8"`` when any matmul leaf is a QTensor, else the param dtype
    name — the ``weights_dtype`` provenance field of ServeReport."""
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QTensor)
    )
    if any(isinstance(leaf, QTensor) for leaf in leaves):
        return "int8"
    return str(jax.tree_util.tree_leaves(params)[0].dtype)


@dataclasses.dataclass
class CalibrationReport:
    """Quantized-vs-f32 fidelity over the calibration prompts."""

    num_prompts: int
    num_positions: int  # real (unpadded) positions compared
    logit_mae: float  # mean |logit_q - logit_f32| over real positions
    logit_mae_max: float  # worst single position's mean-abs-error
    greedy_agreement: float  # fraction of positions with equal argmax
    method: str
    percentile: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def calibrate_params(
    params: PyTree,
    prompts: Sequence[Sequence[int]],
    *,
    num_heads: int,
    method: str = "absmax",
    percentile: float = 99.9,
    block: Optional[int] = None,
    attention: str = "dense",
):
    """Quantize the weights, then measure them: run each calibration
    prompt through the f32 and the quantized forward and compare logits
    position-by-position.

    Prompts are padded to one rectangular batch (a single compile) and
    only REAL positions enter the stats.  Returns ``(qparams, report)``.
    """
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
    )

    if not prompts:
        raise ValueError("calibration needs at least one prompt")
    if any(len(p) < 1 for p in prompts):
        raise ValueError("empty calibration prompt")
    qparams = quantize_params(
        params, method=method, percentile=percentile, block=block
    )

    lens = [len(p) for p in prompts]
    S = max(lens)
    tokens = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, : len(p)] = np.asarray(p, np.int32)
    tokens = jnp.asarray(tokens)

    fwd = jax.jit(
        lambda ps, t: forward(ps, t, num_heads=num_heads, attention=attention)
    )
    logits_f = np.asarray(fwd(params, tokens), np.float32)
    logits_q = np.asarray(fwd(qparams, tokens), np.float32)

    maes: List[float] = []
    agree = 0
    total = 0
    for i, n in enumerate(lens):
        err = np.abs(logits_q[i, :n] - logits_f[i, :n])  # [n, vocab]
        maes.extend(err.mean(axis=-1).tolist())
        agree += int(
            (logits_q[i, :n].argmax(-1) == logits_f[i, :n].argmax(-1)).sum()
        )
        total += n
    report = CalibrationReport(
        num_prompts=len(prompts),
        num_positions=total,
        logit_mae=round(float(np.mean(maes)), 6),
        logit_mae_max=round(float(np.max(maes)), 6),
        greedy_agreement=round(agree / total, 4),
        method=method,
        percentile=percentile if method == "percentile" else None,
    )
    return qparams, report
