"""Pipeline-parallel transformer (models/pipelined_transformer.py).

The model-level consumer of the pipe axis: forward and gradients through
``forward_pipelined`` must match the sequential scan-over-layers path, and
a few SGD steps must actually reduce the causal-LM loss.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models import pipelined_transformer as pt
from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward,
    forward_pipelined,
    init_params,
    next_token_loss,
)
from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh

CFG = dict(num_layers=4, d_model=32, num_heads=4, d_ff=64, vocab_size=97,
           max_len=16)
HEADS = CFG["num_heads"]


@pytest.fixture(scope="module")
def setup():
    params = init_params(jax.random.key(0), **CFG)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, CFG["vocab_size"], (8, 16)),
        jnp.int32,
    )
    return params, tokens


def test_pipelined_forward_matches_sequential(setup):
    params, tokens = setup
    mesh = create_mesh(MeshSpec(pipe=2))
    want = forward(params, tokens, num_heads=HEADS)
    got = forward_pipelined(
        params, tokens, num_heads=HEADS, mesh=mesh, num_microbatches=2
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_pipelined_gradients_match_sequential(setup):
    params, tokens = setup
    mesh = create_mesh(MeshSpec(pipe=4))

    def loss_seq(p):
        return next_token_loss(forward(p, tokens, num_heads=HEADS), tokens)

    def loss_pipe(p):
        return next_token_loss(
            forward_pipelined(
                p, tokens, num_heads=HEADS, mesh=mesh, num_microbatches=2
            ),
            tokens,
        )

    g_seq = jax.grad(loss_seq)(params)
    g_pipe = jax.grad(loss_pipe)(params)
    flat_seq = jax.tree_util.tree_leaves(g_seq)
    flat_pipe = jax.tree_util.tree_leaves(g_pipe)
    for a, b in zip(flat_pipe, flat_seq):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3
        )


def test_pipelined_training_reduces_loss(setup):
    params, tokens = setup
    mesh = create_mesh(MeshSpec(pipe=2, data=4))

    @jax.jit
    def step(p):
        def loss(p):
            return next_token_loss(
                forward_pipelined(
                    p, tokens, num_heads=HEADS, mesh=mesh, num_microbatches=2
                ),
                tokens,
            )

        l, g = jax.value_and_grad(loss)(p)
        return jax.tree.map(lambda w, gw: w - 0.5 * gw, p, g), l

    losses = []
    p = params
    for _ in range(5):
        p, l = step(p)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(l) for l in losses)


def test_layer_count_must_divide_stages(setup):
    params, tokens = setup
    mesh = create_mesh(MeshSpec(pipe=8))  # 4 layers / 8 stages
    with pytest.raises(ValueError, match="not divisible"):
        forward_pipelined(
            params, tokens, num_heads=HEADS, mesh=mesh, num_microbatches=1
        )


def test_bf16_params_keep_scan_carry_dtype():
    """Regression: the dense attention path promoted a bf16 residual stream
    to f32 (f32 softmax output flowed into the stream), breaking the
    scan-over-layers carry dtype contract — caught by the round-4 LM bench.
    Both attention paths must run a full forward+grad in bf16."""
    import jax
    import jax.flatten_util
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
        init_params,
        next_token_loss,
    )

    params = init_params(
        jax.random.key(0), num_layers=2, d_model=64, num_heads=4, d_ff=128,
        vocab_size=97, max_len=32,
    )
    bf16_params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), params
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 97, (2, 32)), jnp.int32
    )
    for attention in ("dense", "flash"):
        logits = forward(bf16_params, toks, num_heads=4, attention=attention)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        grads = jax.grad(
            lambda p, a=attention: next_token_loss(
                forward(p, toks, num_heads=4, attention=a).astype(
                    jnp.float32
                ),
                toks,
            )
        )(bf16_params)
        flat, _ = jax.flatten_util.ravel_pytree(
            jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        )
        assert np.isfinite(np.asarray(flat)).all()


def test_remat_matches_no_remat():
    """remat=True must be a pure memory/time trade: identical logits and
    gradients to the plain scan (jax.checkpoint changes scheduling, not
    math)."""
    import jax
    import jax.flatten_util
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
        init_params,
        next_token_loss,
    )

    params = init_params(
        jax.random.key(2), num_layers=3, d_model=48, num_heads=2, d_ff=96,
        vocab_size=89, max_len=24,
    )
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, 89, (2, 24)), jnp.int32
    )

    def loss(p, remat):
        return next_token_loss(
            forward(p, toks, num_heads=2, remat=remat), toks
        )

    np.testing.assert_allclose(
        float(loss(params, False)), float(loss(params, True)), rtol=1e-6
    )
    g0, _ = jax.flatten_util.ravel_pytree(
        jax.grad(lambda p: loss(p, False))(params)
    )
    g1, _ = jax.flatten_util.ravel_pytree(
        jax.grad(lambda p: loss(p, True))(params)
    )
    np.testing.assert_allclose(
        np.asarray(g0), np.asarray(g1), atol=1e-6, rtol=1e-5
    )


@pytest.mark.parametrize("loss_chunk", [None, 5, 23])
def test_per_token_loss_matches_full_logits(loss_chunk):
    """The chunked head-matmul+CE (per_token_loss) must equal the one-shot
    next_token_loss(forward(...)) in value and gradients — the fusion is a
    memory transform, not a different loss."""
    import jax.flatten_util

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        per_token_loss,
    )

    params = init_params(
        jax.random.key(4), num_layers=2, d_model=32, num_heads=2, d_ff=64,
        vocab_size=131, max_len=24,
    )
    toks = jnp.asarray(
        np.random.default_rng(9).integers(0, 131, (2, 24)), jnp.int32
    )  # s-1 = 23: chunk 23 = single chunk, chunk 5 would not divide -> use 23
    if loss_chunk == 5:
        toks = toks[:, :21]  # s-1 = 20, divisible by 5

    def full(p):
        return next_token_loss(forward(p, toks, num_heads=2), toks)

    def chunked(p):
        return per_token_loss(
            p, toks, num_heads=2, loss_chunk=loss_chunk
        ).mean()

    np.testing.assert_allclose(
        float(full(params)), float(chunked(params)), rtol=1e-6
    )
    g0, _ = jax.flatten_util.ravel_pytree(jax.grad(full)(params))
    g1, _ = jax.flatten_util.ravel_pytree(jax.grad(chunked)(params))
    np.testing.assert_allclose(
        np.asarray(g0), np.asarray(g1), atol=1e-6, rtol=1e-5
    )


def test_per_token_loss_chunk_must_divide():
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        per_token_loss,
    )

    params = init_params(
        jax.random.key(4), num_layers=2, d_model=32, num_heads=2, d_ff=64,
        vocab_size=131, max_len=24,
    )
    toks = jnp.zeros((1, 24), jnp.int32)
    with pytest.raises(ValueError, match="loss_chunk"):
        per_token_loss(params, toks, num_heads=2, loss_chunk=7)


def test_zero3_pipelined_matches_sequential():
    """pipe×fsdp with zero3_axis: stage weights width-sharded over fsdp and
    all-gathered per tick must reproduce the sequential forward AND its
    gradients exactly (the gather reconstructs the full weights)."""
    mesh = create_mesh(MeshSpec(pipe=2, fsdp=2))  # data absorbs the rest
    params = init_params(
        jax.random.key(11), num_layers=4, d_model=32, num_heads=2,
        d_ff=64, vocab_size=64, max_len=16,
    )
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (8, 16)), jnp.int32
    )

    def run_pipe(p):
        return forward_pipelined(
            p, toks, num_heads=2, mesh=mesh, num_microbatches=2,
            zero3_axis="fsdp",
        )

    got = run_pipe(params)
    want = forward(params, toks, num_heads=2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5
    )

    g_pipe = jax.grad(lambda p: (run_pipe(p) ** 2).mean())(params)
    g_seq = jax.grad(lambda p: (forward(p, toks, num_heads=2) ** 2).mean())(
        params
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        g_pipe,
        g_seq,
    )


def test_zero3_wires_param_partition(monkeypatch):
    """forward_pipelined(zero3_axis=...) must hand pipeline_apply a width
    param_partition (the in-stage ZeRO-3 mechanism) and None without it —
    the wiring a boundary-reshard regression would silently drop."""
    from distributeddeeplearning_tpu.ops import pipeline as pipeline_mod

    captured = {}
    real = pipeline_mod.pipeline_apply

    def spy(*args, **kwargs):
        captured["param_partition"] = kwargs.get("param_partition")
        return real(*args, **kwargs)

    monkeypatch.setattr(pipeline_mod, "pipeline_apply", spy)
    mesh = create_mesh(MeshSpec(pipe=2, fsdp=2))
    params = init_params(
        jax.random.key(0), num_layers=2, d_model=32, num_heads=2, d_ff=64,
        vocab_size=64, max_len=16,
    )
    toks = jnp.zeros((8, 16), jnp.int32)

    forward_pipelined(
        params, toks, num_heads=2, mesh=mesh, num_microbatches=2,
        zero3_axis="fsdp",
    )
    part = captured["param_partition"]
    assert part["qkv"] == (None, None, "fsdp")
    assert part["proj"] == (None, "fsdp", None)
    assert part["w_in"] == (None, None, "fsdp")
    assert part["w_out"] == (None, "fsdp", None)
    assert part["ln1"] is None and part["ln2"] is None

    forward_pipelined(
        params, toks, num_heads=2, mesh=mesh, num_microbatches=2,
    )
    assert captured["param_partition"] is None


def test_zero3_rejects_indivisible_width():
    import pytest

    mesh = create_mesh(MeshSpec(pipe=2, fsdp=4))
    params = init_params(
        jax.random.key(0), num_layers=2, d_model=6, num_heads=2, d_ff=10,
        vocab_size=64, max_len=16,
    )
    with pytest.raises(ValueError, match="must divide"):
        forward_pipelined(
            params, jnp.zeros((8, 16), jnp.int32), num_heads=2, mesh=mesh,
            num_microbatches=2, zero3_axis="fsdp",
        )


SEAM_FORWARDS = (
    "forward", "forward_prefill", "forward_decode", "forward_decode_paged",
    "forward_prefill_chunk", "forward_verify", "forward_verify_paged",
)


def _call_at_tiny_geometry(name, params):
    """One of the seven forwards, by name, on 2 slots of 2 pages of 4."""
    L, hd = CFG["num_layers"], CFG["d_model"] // HEADS
    B, PS, NB, K1 = 2, 4, 2, 3
    dense = {n: jnp.zeros((B, L, NB * PS, HEADS, hd)) for n in ("k", "v")}
    pool = {n: jnp.zeros((1 + B * NB, L, PS, HEADS, hd)) for n in ("k", "v")}
    seq = jnp.zeros((B, 8), jnp.int32)
    tok = pos = draft_len = jnp.zeros((B,), jnp.int32)
    draft = jnp.zeros((B, K1), jnp.int32)
    tables = 1 + jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    args, paged = {
        "forward": ((seq,), False),
        "forward_prefill": ((seq,), False),
        "forward_decode": ((tok, dense, pos), False),
        "forward_decode_paged": ((tok, pool, pos, tables), True),
        "forward_prefill_chunk": (
            (seq[:1, :PS], pool, tables[0], jnp.int32(PS)), True),
        "forward_verify": ((draft, dense, pos, draft_len), False),
        "forward_verify_paged": ((draft, pool, pos, draft_len, tables), True),
    }[name]
    kwargs = dict(page_size=PS) if paged else {}
    return getattr(pt, name)(params, *args, num_heads=HEADS, **kwargs)


@pytest.mark.parametrize("name", SEAM_FORWARDS)
def test_every_forward_runs_the_one_layer(name, setup, monkeypatch):
    """The layer is written once (``_block``) and a forward supplies only
    how keys and values are addressed: every ``_layer_norm`` of a traced
    forward is called from ``_block``, two a trace of the scan body.  A
    forward that writes the layer out again fails here."""
    callers = []
    layer_norm = pt._layer_norm

    def counted(x, scale):
        callers.append(sys._getframe(1).f_code.co_name)
        return layer_norm(x, scale)

    monkeypatch.setattr(pt, "_layer_norm", counted)
    jax.eval_shape(lambda p: _call_at_tiny_geometry(name, p), setup[0])
    assert callers == ["_block"] * 2


@pytest.mark.parametrize("name", SEAM_FORWARDS)
def test_every_forward_reads_bf16_operand_weights(name, setup, monkeypatch):
    """What a serving engine holds on a TPU (``quant.bf16_matmul_params``:
    the float32 matmul weights rounded to bf16 once) runs under every
    forward as the float32 program it was, with each matmul the product of
    BOTH operands rounded to bf16, accumulated in float32: equal (up to
    the order of that accumulation) to the float32 tree under a matmul
    that rounds both operands itself, and clearly not the unrounded
    product.  Logits, the residual stream and every cache leaf a forward
    returns stay float32."""
    from distributeddeeplearning_tpu.quant import bf16_matmul_params

    params = setup[0]
    held = bf16_matmul_params(params)
    got = _call_at_tiny_geometry(name, held)
    unrounded = _call_at_tiny_geometry(name, params)

    def both_rounded(x, w):
        assert x.dtype == jnp.float32 and w.dtype == jnp.float32
        r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return jnp.matmul(r(x), r(w), precision="highest")

    monkeypatch.setattr(pt, "_mm", both_rounded)
    want = _call_at_tiny_geometry(name, params)

    got, want, unrounded = (
        jax.tree_util.tree_leaves(t) for t in (got, want, unrounded)
    )
    assert [a.dtype for a in got] == [jnp.float32] * len(got)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    # logits first: the rounding shows at ten times that bound and more
    # (a hundredth of a logit of this tiny model), so an unrounded
    # product would not pass it
    assert float(jnp.abs(got[0] - unrounded[0]).max()) > 2e-5


def test_bf16_train_step_matmuls_stay_the_line_they_were():
    """The train cell casts parameters and activations alike to bf16:
    no matmul of its forward takes the bf16-weight-under-f32 branch (no
    ``dot_general`` asks for a float32 result of bf16 operands)."""
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), init_params(jax.random.key(0), **CFG)
    )
    tokens = jnp.zeros((2, 8), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p: forward(p, tokens, num_heads=HEADS)
    )(params)

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    found = list(dots(jaxpr.jaxpr))
    assert found
    for eqn in found:
        operands = {str(v.aval.dtype) for v in eqn.invars}
        if operands == {"bfloat16"}:
            assert str(eqn.outvars[0].aval.dtype) == "bfloat16"
