"""Int8 quantization subsystem: weight PTQ + int8 KV cache for serving.

The serve stack pages KV HBM per token (``serve/kv_cache.py``) but every
byte it holds — weights and KV pages — is full precision, so cache
capacity (and therefore admission, batch occupancy, tokens/HBM-byte) is
the binding constraint on traffic.  This package is the standard next
lever on TPU-class hardware (arxiv 2605.25645, 1909.09756): store int8,
compute the matmuls in int8 with f32 rescale, dequantize KV inside the
fused attention programs.

- :mod:`quant.qtensor` — the :class:`QTensor` registered pytree (int8
  values + f32 per-channel/per-block scales), ``quantize``/``dequantize``,
  and ``qdot``: dynamic per-row activation quantization feeding an int8
  ``lax.dot_general`` (int32 accumulation) with an f32 rescale by the
  product of activation and weight scales; plus the per-position-per-head
  KV quantization helpers the cache layouts use.  ``qmatmul`` is the one
  call site every matmul of ``pipelined_transformer`` goes through, told
  apart by what it is handed: a QTensor runs ``qdot``; a bfloat16 weight
  under float32 activations runs one bf16 product accumulated in float32
  (what a TPU computes for the float32 product, the weight rounded
  beforehand); anything else is plain ``@``.
- :mod:`quant.calibrate` — post-training weight quantization of the
  ``pipelined_transformer`` param pytree (absmax and percentile
  observers), with an optional calibration pass over a handful of prompts
  that reports logit MAE / greedy agreement vs the f32 model; and
  ``bf16_matmul_params``, the same leaves rounded to bfloat16 once — not
  a lower precision but the operand form a TPU's float32 matmul reads,
  which the serving engines hold where that is so (``serve/engine.py``).

Entry points: ``ddlt serve --quantize-kv int8 --quantize-weights int8
--calib-prompts N``, ``Checkpointer.restore_params(quantize_weights=
"int8")``, and ``bench.py --quant`` (the ``QUANT_*.json`` artifact).
"""

from distributeddeeplearning_tpu.quant.qtensor import (
    QTensor,
    dequantize,
    dequantize_kv,
    qdot,
    qmatmul,
    quantize,
    quantize_kv,
)
from distributeddeeplearning_tpu.quant.calibrate import (
    AbsmaxObserver,
    CalibrationReport,
    PercentileObserver,
    bf16_matmul_params,
    calibrate_params,
    params_dtype,
    quantize_params,
)

__all__ = [
    "QTensor",
    "quantize",
    "dequantize",
    "qdot",
    "qmatmul",
    "quantize_kv",
    "dequantize_kv",
    "AbsmaxObserver",
    "PercentileObserver",
    "CalibrationReport",
    "calibrate_params",
    "quantize_params",
    "bf16_matmul_params",
    "params_dtype",
]
