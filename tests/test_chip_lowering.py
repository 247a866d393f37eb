"""Every Pallas kernel lowers for TPU — checked on the CPU, no chip needed.

``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas→Mosaic lowering with interpret mode pinned off.  That is where the
block-shape rule (last two block dims divisible by the dtype tile, or equal
to the array dims) and the "only scalars from SMEM" rule are enforced, so a
kernel the TPU compiler would refuse fails here, in tier-1, instead of as an
``error`` finish reason on the chip.  Geometries: the full LM (12 heads of
64, 64-token pages), the served cell's (``galactica-1.3b``: 32 heads of 64,
64-token pages, 64-row chunks), ``hd=128`` with 128-token pages, and a
single head (the one shape at which the block rule happens to hold whatever
the kernel does, which exposes what else the lowering objects to).  Pool
pages come as the pool holds them: heads folded into the minor axis,
``[P, page, h * hd]``, a head read as a lane slice of the page.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

fd = importlib.import_module("distributeddeeplearning_tpu.ops.flash_decode")
fa = importlib.import_module(
    "distributeddeeplearning_tpu.ops.flash_attention"
)

MOSAIC = "tpu_custom_call"
SLOTS, BLOCKS = 8, 8


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    monkeypatch.setattr(fd, "_use_interpret", lambda: False)
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)


def _lower_for_tpu(fn, *args) -> str:
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert MOSAIC in text  # the kernel, not a reference path, was lowered
    return text


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("pool", ["float32", "int8"])
@pytest.mark.parametrize(
    "heads,hd,page",
    [(12, 64, 64), (8, 128, 128), (1, 128, 128), (32, 64, 64)],
)
def test_flash_decode_forms_lower_for_tpu(heads, hd, page, pool):
    """decode, chunk-prefill (full chunk and the smallest tail bucket) and
    K+1-verify over the paged pool, and decode over the dense layout."""
    quantized = pool == "int8"
    pages = SLOTS * BLOCKS + 1
    kv = _sds((pages, page, heads * hd), jnp.int8 if quantized else jnp.float32)
    scale = _sds((pages, page, heads)) if quantized else None
    q3 = _sds((SLOTS, heads, hd))
    pos = _sds((SLOTS,), jnp.int32)
    tables = _sds((SLOTS, BLOCKS), jnp.int32)

    text = _lower_for_tpu(
        functools.partial(
            fd.decode_attention_paged, page_size=page, kernel="pallas"
        ),
        q3, kv, kv, scale, scale, q3, q3, pos, tables,
    )
    assert f"flash_decode_decode_{'int8' if quantized else 'f32'}" in text
    for chunk in (64, 8):
        _lower_for_tpu(
            functools.partial(
                fd.chunk_attention, page_size=page, kernel="pallas"
            ),
            _sds((chunk, heads, hd)), kv, kv, scale, scale,
            _sds((BLOCKS,), jnp.int32), _sds((chunk,), jnp.int32),
        )
    if not quantized:  # speculative verify is f32-only upstream
        _lower_for_tpu(
            functools.partial(
                fd.verify_attention_paged, page_size=page, kernel="pallas"
            ),
            _sds((SLOTS, 5, heads, hd)), kv, kv, tables,
            _sds((SLOTS, 5), jnp.int32),
        )
    seq = page * BLOCKS
    rows = _sds((SLOTS, seq, heads, hd), kv.dtype)
    row_scale = _sds((SLOTS, seq, heads)) if quantized else None
    _lower_for_tpu(
        functools.partial(fd.decode_attention_dense, kernel="pallas"),
        q3, rows, rows, row_scale, row_scale, q3, q3, pos,
    )


@pytest.mark.parametrize(
    "hq,hkv,dk,dv,page", [(64, 4, 192, 128, 128), (16, 2, 96, 64, 64)]
)
def test_gqa_decode_kernel_lowers_for_tpu(hq, hkv, dk, dv, page):
    """The grouped-query form over a pool whose KV heads are folded into the
    minor axis, keys and values of different widths (bfloat16, as served)."""
    pages = SLOTS * BLOCKS + 1
    text = _lower_for_tpu(
        functools.partial(
            fd.decode_attention_gqa_paged, page_size=page, kernel="pallas"
        ),
        _sds((SLOTS, hq, dk), jnp.bfloat16),
        _sds((pages, page, hkv * dk), jnp.bfloat16),
        _sds((pages, page, hkv * dv), jnp.bfloat16),
        _sds((SLOTS,), jnp.int32),
        _sds((SLOTS, BLOCKS), jnp.int32),
    )
    assert "flash_decode_decode_gqa_bfloat16" in text


@pytest.mark.parametrize("heads,hd", [(12, 64), (8, 128)])
def test_flash_attention_forward_and_grad_lower_for_tpu(heads, hd):
    """The trained geometry: batch 8, seq 2048, bf16, causal — forward,
    then dq and dk/dv through the custom VJP."""
    x = _sds((8, 2048, heads, hd), jnp.bfloat16)

    def forward(q, k, v):
        return fa.flash_attention(
            q, k, v, None, dtype=jnp.bfloat16, causal=True
        )

    def loss(q, k, v):
        return forward(q, k, v).astype(jnp.float32).sum()

    assert "flash_attention_fwd" in _lower_for_tpu(forward, x, x, x)
    text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count(MOSAIC) == 3
    assert "flash_attention_bwd_dq" in text
    assert "flash_attention_bwd_dkv" in text


def test_dense_layout_refuses_a_length_it_cannot_tile():
    """The dense layout's synthetic pages: whole up to 128, else a
    multiple-of-8 divisor — a length with none is an error, not a silent
    trip through another program."""
    assert fd.dense_block(48) == 48
    assert fd.dense_block(512) == 128
    assert fd.dense_block(320) == 80
    with pytest.raises(ValueError, match="no multiple-of-8 divisor"):
        fd.dense_block(331)
