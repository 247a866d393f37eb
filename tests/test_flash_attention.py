"""Pallas flash attention (ops/flash_attention.py).

Parity against the plain fused attention (models/bert.py
``dot_product_attention``) on the CPU backend (Pallas interpret mode):
forward values, gradients through the custom VJP, padding-mask handling,
and the BERT encoder end-to-end with the kernel injected.
"""

from __future__ import annotations

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.bert import dot_product_attention
from distributeddeeplearning_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention,
)

B, S, H, D = 2, 64, 4, 32


def _inputs(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shape = (B, S, H, D)
    q = jnp.asarray(rng.standard_normal(shape), dtype)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    lengths = rng.integers(S // 2, S + 1, B)
    mask = jnp.asarray(
        (np.arange(S)[None, :] < lengths[:, None])[:, None, None, :]
    )
    return q, k, v, mask


def test_forward_matches_reference():
    q, k, v, mask = _inputs()
    got = flash_attention(q, k, v, mask, dtype=jnp.float32, block_q=16, block_k=16)
    want = dot_product_attention(q, k, v, mask, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    assert np.isfinite(np.asarray(got)).all()


def test_forward_no_mask_single_block():
    q, k, v, _ = _inputs(1)
    got = flash_attention(q, k, v, None, dtype=jnp.float32, block_q=64, block_k=64)
    want = dot_product_attention(q, k, v, None, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_gradients_match_reference():
    q, k, v, mask = _inputs(2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask, dtype=jnp.float32, block_q=16, block_k=16)
        return (o ** 2).sum()

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, mask, dtype=jnp.float32)
        return (o ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4
        )


def test_bf16_inputs_supported():
    q, k, v, mask = _inputs(3, jnp.bfloat16)
    got = flash_attention(q, k, v, mask, dtype=jnp.bfloat16, block_q=32, block_k=32)
    want = dot_product_attention(q, k, v, mask, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.05
    )


def test_indivisible_seq_rejected():
    q, k, v, mask = _inputs()
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, mask, dtype=jnp.float32, block_q=48, block_k=16)


def test_sharded_flash_matches_reference_on_mesh():
    """make_flash_attention(mesh=...) runs the kernel per-shard under
    shard_map (batch over data axes, heads over tensor) and must agree with
    the unsharded reference."""
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.parallel.sharding import batch_sharding

    mesh = create_mesh(MeshSpec(tensor=2))
    # batch must divide the data axes (4-way with tensor=2 on 8 devices)
    rng = np.random.default_rng(5)
    shape = (8, S, H, D)
    q = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    lengths = rng.integers(S // 2, S + 1, 8)
    mask = jnp.asarray(
        (np.arange(S)[None, :] < lengths[:, None])[:, None, None, :]
    )
    attn = make_flash_attention(block_q=16, block_k=16, mesh=mesh)

    fn = jax.jit(lambda q, k, v, m: attn(q, k, v, m, dtype=jnp.float32))
    got = fn(q, k, v, mask)
    want = dot_product_attention(q, k, v, mask, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    # also with explicitly batch-sharded inputs
    q_s = jax.device_put(q, batch_sharding(mesh))
    got_s = fn(q_s, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_bert_encoder_with_flash_attention():
    """Full model forward with the kernel injected as attention_fn."""
    from distributeddeeplearning_tpu.models import get_model

    tokens = np.asarray(
        np.random.default_rng(0).integers(0, 97, (2, 32)), np.int32
    )
    kwargs = dict(
        num_layers=2, hidden_size=64, num_heads=4, intermediate_size=128,
        vocab_size=97, num_classes=3, max_position_embeddings=32,
        dropout_rate=0.0, dtype=jnp.float32,
    )
    ref = get_model("bert-base", **kwargs)
    fl = get_model(
        "bert-base", **kwargs,
        attention_fn=make_flash_attention(block_q=16, block_k=16),
    )
    variables = ref.init(jax.random.key(0), tokens, train=False)
    out_ref = ref.apply(variables, tokens, train=False)
    out_fl = fl.apply(variables, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(out_fl), np.asarray(out_ref), atol=1e-4, rtol=1e-4
    )


def test_gradients_asymmetric_blocks():
    """The Pallas FA2 backward must be block-shape-agnostic (dq pass streams
    k blocks; dk/dv pass streams q blocks — different grids)."""
    q, k, v, mask = _inputs(5)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, mask, dtype=jnp.float32, block_q=16, block_k=32
        )
        return (o ** 2).sum()

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, mask, dtype=jnp.float32)
        return (o ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4
        )


def test_bf16_gradients_finite():
    q, k, v, mask = _inputs(6, jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(
            q, k, v, mask, dtype=jnp.bfloat16, block_q=32, block_k=32
        )
        return (o.astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, np.float32)).all()


# ---------------------------------------------------------------------------
# Causal mode (VERDICT r03 #2): in-kernel triangle mask + block skip, exact
# against a dense causal oracle in forward and all three gradients, alone
# and combined with key padding.
# ---------------------------------------------------------------------------


def _dense_causal(q, k, v, mask):
    """Dense causal oracle (the pipelined_transformer block's math)."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d, jnp.float32)
    )
    if mask is not None:
        scores = jnp.where(
            jnp.broadcast_to(mask, (b, 1, 1, s)), scores, -1e30
        )
    tri = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(tri[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("block", [16, 32, 64])
def test_causal_forward_matches_dense(block):
    q, k, v, _ = _inputs(3)
    got = flash_attention(
        q, k, v, None, dtype=jnp.float32, block_q=block, block_k=block,
        causal=True,
    )
    want = _dense_causal(q, k, v, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_causal_asymmetric_blocks():
    q, k, v, _ = _inputs(4)
    got = flash_attention(
        q, k, v, None, dtype=jnp.float32, block_q=16, block_k=32, causal=True
    )
    want = _dense_causal(q, k, v, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    got = flash_attention(
        q, k, v, None, dtype=jnp.float32, block_q=32, block_k=16, causal=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_causal_with_padding_mask():
    q, k, v, mask = _inputs(5)
    got = flash_attention(
        q, k, v, mask, dtype=jnp.float32, block_q=16, block_k=16, causal=True
    )
    want = _dense_causal(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_causal_gradients_match_dense():
    q, k, v, mask = _inputs(6)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, mask, dtype=jnp.float32, block_q=16, block_k=16,
            causal=True,
        )
        return (o ** 2).sum()

    def loss_ref(q, k, v):
        return (_dense_causal(q, k, v, mask) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4
        )


def test_causal_first_row_attends_only_itself():
    """Query 0 may see only key 0 — its output must equal v[0] exactly."""
    q, k, v, _ = _inputs(7)
    got = flash_attention(
        q, k, v, None, dtype=jnp.float32, block_q=16, block_k=16, causal=True
    )
    np.testing.assert_allclose(
        np.asarray(got[:, 0]), np.asarray(v[:, 0]), atol=1e-6
    )


def test_pipelined_transformer_flash_matches_dense():
    """The decoder model's attention="flash" path reproduces the dense path
    (logits and parameter gradients) — the VERDICT's 'wired into the decoder'
    requirement, checked end-to-end through forward()."""
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
        init_params,
        next_token_loss,
    )

    params = init_params(
        jax.random.key(0), num_layers=2, d_model=64, num_heads=4, d_ff=128,
        vocab_size=97, max_len=32,
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 97, (2, 32)), jnp.int32
    )
    lg_dense = forward(params, toks, num_heads=4, attention="dense")
    lg_flash = forward(params, toks, num_heads=4, attention="flash")
    np.testing.assert_allclose(
        np.asarray(lg_flash), np.asarray(lg_dense), atol=2e-4, rtol=2e-4
    )

    def loss(p, attention):
        return next_token_loss(
            forward(p, toks, num_heads=4, attention=attention), toks
        )

    g_dense = jax.grad(lambda p: loss(p, "dense"))(params)
    g_flash = jax.grad(lambda p: loss(p, "flash"))(params)
    flat_d, _ = jax.flatten_util.ravel_pytree(g_dense)
    flat_f, _ = jax.flatten_util.ravel_pytree(g_flash)
    np.testing.assert_allclose(
        np.asarray(flat_f), np.asarray(flat_d), atol=5e-4, rtol=5e-4
    )


def test_auto_block_nondivisible_seq():
    """Seq lens divisible by 512 but not 1024 (e.g. 1536) must auto-select
    a smaller block instead of raising — regression for the 1024 default."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.ops.flash_attention import (
        _auto_block,
        flash_attention,
    )

    assert _auto_block(1536) == 512
    assert _auto_block(2048) == 1024
    assert _auto_block(2560) == 512
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 1536, 1, 8)), jnp.float32)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, None, dtype=jnp.float32, causal=True)
    assert out.shape == (1, 1536, 1, 8)
    assert bool(jnp.isfinite(out).all())


def test_auto_block_floor_is_refused_not_rerouted():
    """Low-divisibility seq lens (1032 -> block 8, odd -> 1) must not run
    a pathological (S/b)^2 grid — and must not silently run another
    program either: the wrapper raises, and ``auto_block_tiles`` lets a
    caller check a length up front."""
    import warnings

    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.ops.flash_attention import (
        _auto_block,
        auto_block_tiles,
        flash_attention,
    )

    assert _auto_block(1032) == 8  # the pathological selection itself
    assert not auto_block_tiles(1032) and not auto_block_tiles(2049)
    assert auto_block_tiles(64) and auto_block_tiles(333)  # one tile
    assert auto_block_tiles(1536) and auto_block_tiles(2048)

    rng = np.random.default_rng(1)
    s = 1032
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, s, 1, 8)), jnp.float32)
        for _ in range(3)
    )
    with pytest.raises(ValueError, match="below the 128 floor"):
        flash_attention(q, k, v, None, dtype=jnp.float32, causal=True)

    # seqs at/below the floor keep the kernel: single-tile grids are fine
    q2, k2, v2 = (x[:, :64] for x in (q, k, v))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out2 = flash_attention(q2, k2, v2, None, dtype=jnp.float32,
                               causal=True)
    assert out2.shape == (1, 64, 1, 8)

    # explicit tiny blocks are honoured (caller opted in) — no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out3 = flash_attention(q2, k2, v2, None, dtype=jnp.float32,
                               causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(out2), atol=2e-5)
