"""Flash attention — a Pallas TPU kernel for the transformer hot op.

The single-device analogue of :mod:`ops.ring_attention`: the same
online-softmax recurrence, but blocked over VMEM within one chip instead of
rotated around the ICI ring.  Q/K/V tiles stream HBM→VMEM per grid step and
scores/normalizers never materialize in HBM — memory O(block²) instead of
O(S²), the standard flash-attention scheme (Dao et al. 2205.14135) expressed
in Pallas (see /opt/skills/guides/pallas_guide.md for the kernel idioms).

Grid: ``(batch*heads, q_blocks, k_blocks)`` with the k dimension
"arbitrary" (sequential) so the f32 scratch accumulators (m, l, acc)
carry across k blocks of the same q block.

Differentiation: the kernel is wrapped in ``jax.custom_vjp`` — forward runs
the Pallas kernel and saves the per-query logsumexp; backward is the
FlashAttention-2 blocked scheme (Dao 2307.08691), also in Pallas: a dq pass
(sequential over k blocks) and a dk/dv pass (sequential over q blocks), each
recomputing the attention probabilities of one (q-block, k-block) tile from
the saved logsumexp so nothing O(S²) ever materializes in HBM — training
memory is O(S), which is what makes long-context *training* (not just
inference) fit on a chip.  On non-TPU backends the kernels run in Pallas
interpret mode, so the op is testable on the CPU mesh.

``make_flash_attention()`` returns an ``attention_fn`` drop-in for
``models.bert`` (same signature as ``dot_product_attention``).  The padding
mask arrives as an additive f32 bias so the custom_vjp signature stays
all-float.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30  # finite mask fill; -inf poisons the online-softmax max

# The online softmax runs in base 2: exp(x) = exp2(x·log2e) folded into the
# score scale, because exp2 is the TPU transcendental primitive (exp costs
# an extra multiply per element, and the [bq, bk] exponentials are the
# kernel's dominant VPU work).  The saved logsumexp stays in NATS at the
# interface — callers (ulysses composition, tests) never see base 2.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _causal_tile_bias(row0, col0, bq, bk):
    """Additive triangle mask for one [bq, bk] score tile at global offsets
    (row0, col0): 0 where key_pos <= query_pos, NEG_BIG above the diagonal."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(cols <= rows, 0.0, NEG_BIG).astype(jnp.float32)


def _kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, has_bias: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]  # [bq, D] — native dtype: bf16 inputs ride the MXU's
        k = k_ref[0]  # bf16×bf16→f32 path; casting to f32 first would quarter
        v = v_ref[0]  # the matmul rate
        bq, bk = q.shape[0], k.shape[0]
        # base-2 domain: scores pre-multiplied by log2e, exponentials via
        # exp2 (see LOG2E above)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * (scale * LOG2E)
        )  # [bq, bk] f32, base-2 scaled
        if has_bias:
            # key-padding bias is 0 or NEG_BIG — no rescaling needed, and
            # mask-free callers (the causal LM path) skip the add entirely
            s = s + bias_ref[0, 0][None, :]
        if causal:
            s = s + _causal_tile_bias(qi * bq, ki * bk, bq, bk)

        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp2(s - m_cur)
        correction = jnp.exp2(m_prev - m_cur)
        l_new = l_ref[:, :1] * correction + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # narrow [bq, 1] stores: only lane 0 is ever read back, and the
        # full-width broadcast was 1 MB of redundant VMEM writes per tile
        m_ref[:, :1] = m_cur
        l_ref[:, :1] = l_new

    if causal:
        # Whole-tile skip past the diagonal: k block ki contributes to q
        # block qi only when its first key position can be <= some query
        # position in the block — for the square grid this drops ~half the
        # tiles' matmuls (the causal-FLOP saving).  The accumulators simply
        # carry through skipped steps.
        bq = q_ref.shape[1]
        bk = k_ref.shape[1]
        pl.when(ki * bk <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)  # fully-masked rows stay finite
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # convert the base-2 running max back to a NAT-unit logsumexp
        lse_ref[0, 0] = (m_ref[:, 0] + jnp.log2(l[:, 0])) * LN2


def _flash_fwd_pallas(q3, k3, v3, bias2, *, heads: int, block_q: int,
                      block_k: int, out_dtype, causal: bool = False,
                      has_bias: bool = True):
    """q3/k3/v3: [BH, S, D]; bias2: [B, S] f32 → (o [BH,S,D], lse [BH,S])."""
    bh, s, d = q3.shape
    scale = 1.0 / (d ** 0.5)
    grid = (bh, s // block_q, s // block_k)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               has_bias=has_bias)
    compiler_params = None
    if not _use_interpret():
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]
    # bias/lse ride as 3-D with a size-1 middle axis: TPU block shapes must
    # have their last two dims divisible by (8, 128) or equal to the full
    # array dims, and a full-size 1 satisfies that where a 1-of-B slice
    # would not.
    o3, lse3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, qi, ki, heads=heads: (b // heads, 0, ki),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), out_dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=compiler_params,
        interpret=_use_interpret(),
        name="flash_attention_fwd",
    )(q3, k3, v3, bias2[:, None, :])
    return o3, lse3[:, 0, :]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale: float, causal: bool,
                   has_bias: bool):
    """dq pass: one q block resident, stream k/v blocks (grid dim 2)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]      # [bq], nats
        delta = delta_ref[0, 0]  # [bq] = rowsum(dO ⊙ O)
        bq, bk = q.shape[0], k.shape[0]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * (scale * LOG2E)
        )
        if has_bias:
            s = s + bias_ref[0, 0][None, :]
        if causal:
            s = s + _causal_tile_bias(qi * bq, ki * bk, bq, bk)
        # exact probs from the saved logsumexp, in the base-2 domain:
        # exp(s_nat - lse) == exp2(s_base2 - lse·log2e)
        p = jnp.exp2(s - (lse * LOG2E)[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        bq = q_ref.shape[1]
        bk = k_ref.shape[1]
        pl.when(ki * bk <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, has_bias: bool):
    """dk/dv pass: one k block resident, stream q blocks (grid dim 2).
    Works transposed ([bk, bq] tiles) so the accumulators index by key."""
    ci = pl.program_id(1)  # k-block index (resident)
    qi = pl.program_id(2)  # q-block index (streamed)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]      # [bq], nats
        delta = delta_ref[0, 0]  # [bq]
        bq, bk = q.shape[0], k.shape[0]
        st = (
            jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * (scale * LOG2E)
        )  # [bk, bq], base-2 scaled
        if has_bias:
            st = st + bias_ref[0, 0][:, None]
        if causal:
            # transposed tile: rows are keys (global ci*bk+r), cols are
            # queries (global qi*bq+c); key visible when key_pos <= query_pos
            keys = ci * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            queries = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            st = st + jnp.where(keys <= queries, 0.0, NEG_BIG).astype(
                jnp.float32
            )
        pt = jnp.exp2(st - (lse * LOG2E)[None, :])
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, bq]
        dst = pt * (dpt - delta[None, :]) * scale
        dk_acc[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # k block ci receives gradient only from q blocks whose LAST query
        # position reaches it: qi*bq + bq - 1 >= ci*bk.
        bq = q_ref.shape[1]
        bk = k_ref.shape[1]
        pl.when(qi * bq + bq - 1 >= ci * bk)(_compute)
    else:
        _compute()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q3, k3, v3, bias2, o3, lse, do3, *, heads: int,
                      block_q: int, block_k: int, causal: bool = False,
                      has_bias: bool = True):
    """FlashAttention-2 backward: (dq, dk, dv), each [BH, S, D]."""
    bh, s, d = q3.shape
    scale = 1.0 / (d ** 0.5)
    # delta_i = Σ_d dO ⊙ O — one cheap O(S·D) elementwise reduce in XLA.
    delta = jnp.sum(
        do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1
    )  # [BH, S]
    bias3 = bias2[:, None, :]
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]
    compiler_params = None
    if not _use_interpret():
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    bias_spec = pl.BlockSpec(
        (1, 1, block_k), lambda b, i, j, heads=heads: (b // heads, 0, j)
    )
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          has_bias=has_bias),
        grid=(bh, s // block_q, s // block_k),
        in_specs=[q_spec, k_spec, k_spec, bias_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=compiler_params,
        interpret=_use_interpret(),
        name="flash_attention_bwd_dq",
    )(q3, k3, v3, bias3, do3, lse3, delta3)

    # dk/dv pass: swap the roles — k blocks resident (grid dim 1), q blocks
    # streamed (grid dim 2, sequential).
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    bias_spec2 = pl.BlockSpec(
        (1, 1, block_k), lambda b, i, j, heads=heads: (b // heads, 0, i)
    )
    row_spec2 = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, j))
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          has_bias=has_bias),
        grid=(bh, s // block_k, s // block_q),
        in_specs=[
            q_spec2, k_spec2, k_spec2, bias_spec2, q_spec2, row_spec2, row_spec2
        ],
        out_specs=[k_spec2, k_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=_use_interpret(),
        name="flash_attention_bwd_dkv",
    )(q3, k3, v3, bias3, do3, lse3, delta3)
    return dq3, dk3, dv3


def _make_core(heads: int, block_q: int, block_k: int, out_dtype,
               causal: bool = False, has_bias: bool = True):
    @jax.custom_vjp
    def core(q3, k3, v3, bias2):
        o, _ = _flash_fwd_pallas(
            q3, k3, v3, bias2, heads=heads, block_q=block_q,
            block_k=block_k, out_dtype=out_dtype, causal=causal,
            has_bias=has_bias,
        )
        return o

    def fwd(q3, k3, v3, bias2):
        o, lse = _flash_fwd_pallas(
            q3, k3, v3, bias2, heads=heads, block_q=block_q,
            block_k=block_k, out_dtype=out_dtype, causal=causal,
            has_bias=has_bias,
        )
        return o, (q3, k3, v3, bias2, o, lse)

    def bwd(res, do):
        q3, k3, v3, bias2, o, lse = res
        dq, dk, dv = _flash_bwd_pallas(
            q3, k3, v3, bias2, o, lse, do.astype(q3.dtype),
            heads=heads, block_q=block_q, block_k=block_k, causal=causal,
            has_bias=has_bias,
        )
        return dq, dk, dv, jnp.zeros_like(bias2)

    core.defvjp(fwd, bwd)
    return core


def _auto_block(s: int, cap: int = 1024) -> int:
    """Largest power-of-two-descending divisor of ``s`` up to ``cap``.

    1024 measured 15-25% faster than 512 on a v5e at seq 2048-32k (the
    [bq, bk] f32 score tile is 4 MB of the 16 MB scoped VMEM; 2048-wide
    tiles exceed the limit and fail to compile), so auto-selection starts
    there and halves until it divides S — seq 1536 gets 512, not an error.

    Sequence lengths with low power-of-two divisibility land on tiny
    blocks (1032 → 8, odd → 1) whose (S/b)² grids are pathological;
    :func:`flash_attention` refuses them below ``AUTO_BLOCK_FLOOR``.
    """
    b = min(cap, s)
    while s % b:
        b //= 2
    return b


# Auto-selected blocks below this run a pathological (S/b)² grid, which
# the wrapper refuses.  S itself below the floor is fine (the grid is a
# single tile), so the effective floor is min(S, 128).
AUTO_BLOCK_FLOOR = 128


def auto_block_tiles(s: int) -> bool:
    """Whether auto-selected blocks tile a length-``s`` sequence at or
    above the floor — callers that pick sequence lengths (the dense serve
    engine's prompt buckets, the LM workload's ``seq_len``) check this up
    front instead of discovering the refusal mid-run."""
    return _auto_block(s) >= min(s, AUTO_BLOCK_FLOOR)


def _dense_attention(q, k, v, mask, *, dtype, causal):
    """Reference dense attention with the kernel's exact semantics (f32
    softmax, key-padding mask, causal triangle) — what the tests and
    ``chip_smoke.py`` compare the kernel against."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * (
        1.0 / d ** 0.5
    )
    if mask is not None:
        key_mask = jnp.broadcast_to(mask, (b, 1, 1, s))
        scores = jnp.where(key_mask, scores, NEG_BIG)
    if causal:
        scores = jnp.where(
            jnp.tril(jnp.ones((s, s), bool))[None, None], scores, NEG_BIG
        )
    attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v).astype(dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    *,
    dtype: jnp.dtype,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    causal: bool = False,
) -> jax.Array:
    """Drop-in for ``models.bert.dot_product_attention``: [B, S, H, D] in/out.

    ``mask``: bool, broadcastable to [B, 1, 1, S] (key padding).  Blocks
    default to auto-selection (:func:`_auto_block`: 1024 or the largest
    halving that divides S); explicit blocks clamp to the sequence length
    and S must be divisible by them.

    ``causal=True`` applies the autoregressive triangle (key_pos <=
    query_pos) INSIDE the kernel — fully-masked k-tiles skip their matmuls
    entirely (≈2× fewer FLOPs at long S), the diagonal tiles mask
    elementwise, and the same skip logic runs in both backward passes.
    Composes with the key-padding ``mask``.
    """
    b, s, h, d = q.shape
    auto_q, auto_k = block_q is None, block_k is None
    block_q = _auto_block(s) if auto_q else min(block_q, s)
    block_k = _auto_block(s) if auto_k else min(block_k, s)
    floor = min(s, AUTO_BLOCK_FLOOR)
    if (auto_q and block_q < floor) or (auto_k and block_k < floor):
        # Low power-of-two divisibility (1032 → block 8, odd S → 1): the
        # (S/b)² grid compiles and runs pathologically.  Refused, not
        # rerouted to dense: a caller that asked for the kernel must not
        # silently get another program.
        raise ValueError(
            f"flash_attention: seq len {s} auto-selects block "
            f"({block_q}, {block_k}) below the {AUTO_BLOCK_FLOOR} floor — "
            f"pad the sequence to a multiple of {AUTO_BLOCK_FLOOR}, pass "
            "explicit block_q/block_k, or ask for dense attention"
        )
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq len {s} not divisible by blocks ({block_q}, {block_k})"
        )
    if mask is None:
        bias2 = jnp.zeros((b, s), jnp.float32)
    else:
        key_mask = jnp.broadcast_to(mask, (b, 1, 1, s))[:, 0, 0, :]
        bias2 = jnp.where(key_mask, 0.0, NEG_BIG).astype(jnp.float32)

    to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)  # noqa: E731
    core = _make_core(h, block_q, block_k, dtype, causal,
                      has_bias=mask is not None)
    o3 = core(to3(q), to3(k), to3(v), bias2)
    return o3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def make_flash_attention(block_q: Optional[int] = None,
                         block_k: Optional[int] = None, mesh=None,
                         causal: bool = False):
    """Bind block sizes → an ``attention_fn`` for the transformer models.

    With a multi-device ``mesh`` the kernel runs per-shard inside
    ``shard_map`` — batch over the (data, fsdp) axes, heads over ``tensor``,
    sequence replicated (sequence sharding is :func:`ops.ring_attention`'s
    job).  A bare ``pallas_call`` cannot be partitioned by GSPMD, so without
    this wrap a sharded caller would gather the global batch onto every chip.

    ``causal=True`` binds the in-kernel triangle mask (decoder models).
    """

    def _local(q, k, v, mask, dtype):
        return flash_attention(
            q, k, v, mask, dtype=dtype, block_q=block_q, block_k=block_k,
            causal=causal,
        )

    def attention_fn(q, k, v, mask, *, dtype):
        if mesh is None or mesh.devices.size == 1:
            return _local(q, k, v, mask, dtype)

        from distributeddeeplearning_tpu.parallel import sharding as _layout
        from distributeddeeplearning_tpu.parallel.compat import shard_map

        qkv_spec, mask_spec = _layout.tp_attention_specs(q.shape, mesh)
        if mask is None:
            # keep mask=None through the shard_map so the kernels compile
            # with has_bias=False — fabricating an all-ones mask here would
            # silently re-introduce the per-tile bias loads/adds the
            # unmasked (causal-LM) path skips
            return shard_map(
                lambda q, k, v: _local(q, k, v, None, dtype),
                mesh=mesh,
                in_specs=(qkv_spec, qkv_spec, qkv_spec),
                out_specs=qkv_spec,
            )(q, k, v)
        mask = jnp.broadcast_to(mask, (q.shape[0], 1, 1, q.shape[1]))
        return shard_map(
            lambda q, k, v, m: _local(q, k, v, m, dtype),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
        )(q, k, v, mask)

    return attention_fn
