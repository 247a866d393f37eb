"""QTensor: an int8-values + f32-scales pytree, and the int8 matmul.

Symmetric int8 quantization throughout: ``x ≈ values * scales`` with
``values`` in [-127, 127] (the -128 code is left unused so the grid is
symmetric and ``|dequant| <= amax`` exactly).  Scales are stored with
``keepdims`` so dequantization is a plain broadcast multiply, and the
quantized axis is addressed NEGATIVELY (``axis=-2`` for a ``[..., K, N]``
weight contracted over K) so a stacked ``[L, K, N]`` leaf scanned by
``lax.scan`` yields per-layer ``[K, N]`` QTensors whose static metadata
is still correct — the property that lets a quantized params pytree flow
through the existing scan-over-layers forwards unchanged.

``qdot`` is the compute path: activations are quantized dynamically
per-row (per-token absmax over the contraction dim — the W8A8 scheme
hardware int8 units want), the matmul runs as an int8×int8
``lax.dot_general`` with ``preferred_element_type=int32`` (no overflow:
127·127·K fits int32 for any realistic K), and the int32 accumulator is
rescaled once by the OUTER PRODUCT of activation and weight scales.
Block-quantized or non-standard-axis weights fall back to
dequantize-then-matmul (correct, just not int8 compute).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: Largest int8 code used; -128 stays unused (symmetric grid).
QMAX = 127.0
#: Floor on scales so an all-zero channel divides cleanly to zeros.
EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class QTensor:
    """Quantized tensor: ``dequant = values.astype(f32) * scales``.

    ``values``: int8; ``scales``: f32 with keepdims shape (broadcastable
    against ``values``); ``axis``: the NEGATIVE index of the reduced
    (contraction) dim the scales were computed over; ``block``: tokens
    per scale block along ``axis`` (None = whole-axis per-channel).
    """

    values: jax.Array
    scales: jax.Array
    axis: int = -2
    block: Optional[int] = None

    # array-protocol conveniences so shape-probing code (engine dim
    # validation, CLI vocab checks) works on quantized leaves unchanged
    @property
    def shape(self) -> Tuple[int, ...]:
        return self.values.shape

    @property
    def ndim(self) -> int:
        return self.values.ndim

    @property
    def dtype(self):
        return self.values.dtype

    def __repr__(self) -> str:  # keep pytree dumps readable
        return (
            f"QTensor(int8{list(self.values.shape)}, "
            f"scales{list(self.scales.shape)}, axis={self.axis}, "
            f"block={self.block})"
        )


def _flatten(qt: QTensor):
    return (qt.values, qt.scales), (qt.axis, qt.block)


def _flatten_with_keys(qt: QTensor):
    # Named child keys (".values" / ".scales") so path-walking consumers —
    # the partition-rule layout engine in ``parallel.sharding`` resolves
    # leaves by name — see readable paths instead of flat indices.
    return (
        (jax.tree_util.GetAttrKey("values"), qt.values),
        (jax.tree_util.GetAttrKey("scales"), qt.scales),
    ), (qt.axis, qt.block)


def _unflatten(aux, children) -> QTensor:
    values, scales = children
    axis, block = aux
    return QTensor(values, scales, axis, block)


jax.tree_util.register_pytree_with_keys(
    QTensor, _flatten_with_keys, _unflatten, flatten_func=_flatten
)


def _amax(x: jax.Array, axis: int, observer=None) -> jax.Array:
    """Per-channel max-abs over ``axis`` (keepdims); ``observer``
    overrides the reduction (``calibrate.PercentileObserver`` clips
    outliers so the grid spends its 8 bits on the bulk)."""
    if observer is not None:
        return observer(x, axis)
    return jnp.max(jnp.abs(x), axis=axis, keepdims=True)


def quantize(
    x: jax.Array,
    *,
    axis: int = -2,
    block: Optional[int] = None,
    observer=None,
) -> QTensor:
    """Quantize ``x`` to int8 with per-channel (or per-block) f32 scales.

    ``axis`` is the reduced dim, addressed negatively (default -2: the
    contraction dim of a ``[..., K, N]`` matmul weight, i.e. per-OUTPUT-
    channel scales).  ``block`` splits that dim into ``block``-sized
    groups with one scale each — finer grid for weights whose channel
    range is dominated by a few rows.
    """
    if axis >= 0:
        axis = axis - x.ndim  # normalize to the negative convention
    x = x.astype(jnp.float32)
    if block is not None:
        K = x.shape[axis]
        if K % block:
            raise ValueError(f"block {block} must divide dim {K} (axis {axis})")
        # [..., K, ...] -> [..., K//block, block, ...]; scale per block
        split = x.ndim + axis
        xb = x.reshape(*x.shape[:split], K // block, block, *x.shape[split + 1:])
        # splitting K -> (K//block, block) leaves the block dim at the
        # same NEGATIVE index `axis` pointed at (the group dim lands one
        # position earlier), so the reduction axis is unchanged
        amax = _amax(xb, axis, observer)
        scales = jnp.maximum(amax, EPS) / QMAX
        values = jnp.clip(jnp.round(xb / scales), -QMAX, QMAX)
        return QTensor(
            values.reshape(x.shape).astype(jnp.int8),
            scales,
            axis,
            block,
        )
    amax = _amax(x, axis, observer)
    scales = jnp.maximum(amax, EPS) / QMAX
    values = jnp.clip(jnp.round(x / scales), -QMAX, QMAX).astype(jnp.int8)
    return QTensor(values, scales, axis, None)


def dequantize(qt: QTensor, dtype=jnp.float32) -> jax.Array:
    """``values * scales`` back to ``dtype`` (exact for the stored grid)."""
    v = qt.values.astype(jnp.float32)
    if qt.block is not None:
        axis = qt.axis
        split = v.ndim + axis
        K = v.shape[axis]
        vb = v.reshape(
            *v.shape[:split], K // qt.block, qt.block, *v.shape[split + 1:]
        )
        return (vb * qt.scales).reshape(v.shape).astype(dtype)
    return (v * qt.scales).astype(dtype)


def qdot(x: jax.Array, qt: QTensor) -> jax.Array:
    """``x @ qt`` with int8 compute: ``x [..., K] @ w [K, N] -> [..., N]``.

    Activations quantize dynamically per row (absmax over K — one scale
    per token, following the separate-activation/weight-scale scheme of
    production int8 serving stacks), the contraction runs int8×int8 with
    int32 accumulation, and ONE f32 multiply applies
    ``a_scale ⊗ w_scale``.  Non-2D / block-quantized / nonstandard-axis
    weights take the dequantize fallback — same math, f32 compute.
    """
    if qt.values.ndim != 2 or qt.axis != -2 or qt.block is not None:
        return x @ dequantize(qt, x.dtype)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    a_scale = jnp.maximum(amax, EPS) / QMAX  # [..., 1]
    xq = jnp.clip(
        jnp.round(x.astype(jnp.float32) / a_scale), -QMAX, QMAX
    ).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq,
        qt.values,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [..., N] int32
    w_scale = qt.scales.reshape(-1)  # [N] (keepdims [1, N] flattened)
    return (acc.astype(jnp.float32) * a_scale * w_scale).astype(x.dtype)


def qmatmul(x: jax.Array, w) -> jax.Array:
    """The matmul dispatch the model forwards use — ONE call site per
    matmul, told apart by what it is handed, so an f32, a bf16-weight and
    a quantized params pytree run the identical program structure:

    - QTensor ``w``: the int8 path (:func:`qdot`);
    - bfloat16 ``w`` under float32 ``x`` (a serving engine's weights
      rounded once, ``quant.calibrate.bf16_matmul_params``): ``x`` rounded
      to bf16, one bf16 product, float32 accumulation and result — the
      product a TPU computes for ``x @ w`` on float32 operands at default
      precision, without rounding ``w`` again on every call;
    - everything else (f32 @ f32; bf16 @ bf16, the bf16 train step):
      plain ``@``.
    """
    if isinstance(w, QTensor):
        return qdot(x, w)
    if w.dtype == jnp.bfloat16 and x.dtype == jnp.float32:
        return jax.lax.dot_general(
            x.astype(jnp.bfloat16),
            w,
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return x @ w


# --------------------------------------------------------------------------
# KV-cache quantization: per-position-per-head scales.
#
# KV pages are written incrementally (one token per decode step, one chunk
# per prefill step), so the scale granularity must be at most one WRITE:
# a page-granular scale would need requantizing the whole page on every
# token append (growing the scale re-decodes every earlier int8 code to a
# larger value — lossy in exactly the positions attention re-reads).  One
# f32 scale per (position, head) over the head_dim vector keeps every
# write independent: overhead 4 bytes per head-position against head_dim
# int8 bytes (hd=64 → 6.25%; total int8 KV = 26.6% of f32).
# --------------------------------------------------------------------------


def quantized_cache(cache) -> bool:
    """True when a KV-cache pytree carries the int8 layout's scale leaves
    (``{"k", "v", "k_scale", "v_scale"}``) — THE layout predicate, shared
    by the model forwards and the serve cache accounting so the two can
    never disagree about what counts as quantized."""
    return "k_scale" in cache


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantize K/V vectors ``[..., h, hd] -> (int8 [..., h, hd],
    f32 scales [..., h])`` — one scale per head per position."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax, EPS) / QMAX  # [..., h]
    values = jnp.clip(jnp.round(x / scale[..., None]), -QMAX, QMAX)
    return values.astype(jnp.int8), scale


def dequantize_kv(
    values: jax.Array, scale: jax.Array, dtype=jnp.float32
) -> jax.Array:
    """``[..., h, hd] int8 * [..., h] -> [..., h, hd]`` in ``dtype`` —
    the multiply XLA fuses into the attention einsum that consumes it."""
    return (values.astype(jnp.float32) * scale[..., None]).astype(dtype)
