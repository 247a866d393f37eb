"""Paged flash-decode: block-table-aware attention over the KV cache.

The serving-side sibling of :mod:`ops.flash_attention`, built for ROADMAP
Open item 2(a): QUANT_r10 showed int8 KV pages win 3.76x capacity but LOSE
decode speed, and OBS_r11 machine-attributed the regression to the
attention consuming a block-table-gathered, fully *dequantized* f32
history.  This module makes the attention read quantized bytes all the way
into the tile:

- **Pallas kernel** (:func:`_pallas_attention`): grid ``(slots,
  history_blocks)`` with the history dimension sequential and every head
  of the page handled per step — an online-softmax split-K over the
  slot's pages; the one-row float32 decode takes all heads in one
  block-diagonal matmul a page (:func:`_kernel_decode`).  Block tables ride
  as scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so each K/V tile's
  ``BlockSpec`` index_map resolves ``logical page j -> physical page
  tables[b, j]`` (pages past the slot's newest position repeat its last
  one and fetch nothing) and the pages stream HBM→VMEM **directly** — the gathered
  ``[b, s, h, hd]`` history never exists as an array.  Int8 pools
  dequantize *inside the tile*: ``kf = k_int8 · scale[pos, head]`` at
  ``[page_size, hd]`` granularity, so f32 history never exists in HBM at
  all.  Runs in interpret mode off-TPU (same pattern as
  ``ops.flash_attention``), which is how tier-1 pins its math on CPU;
  ``tests/test_tpu_lowering.py`` pins that every form lowers for TPU.

- **Fused-XLA twin** (the ``_xla_*`` paths): the same read discipline
  expressed in XLA for backends where interpret-mode Pallas would be an
  emulation, not a kernel.  For f32 pools it is op-for-op the legacy
  gather path (bitwise identical — the decode==full-forward pin extends
  through it for free).  For int8 pools the per-(position, head) scales
  FOLD into the ``[b, h, s]`` score/probability vectors instead of
  scaling the ``[b, s, h, hd]`` history: the only history-sized f32 value
  left is the bare int8→f32 widening feeding the matmul, and the scale
  multiply / own-token select that made the old path slow (and that the
  dtype audit now bans at history granularity) are gone.  Measured on the
  bench geometry this turns the int8 decode step from +8% slower than f32
  into faster than f32 — the both-axes win QUANT_r15 gates on.

- **Legacy gather** (the ``_gather_*`` paths): the pre-kernel code moved
  here verbatim from ``models.pipelined_transformer`` — still the
  reference every flash variant is pinned against
  (``tests/test_flash_decode.py``), and still selectable end-to-end via
  ``--decode-kernel gather``.

Kernel selection (:func:`resolve_kernel`): ``"auto"`` → ``"flash"``;
``"flash"`` runs the Pallas kernel on TPU and the fused-XLA twin
elsewhere — the platform alone decides (:func:`flash_impl`, which the
engines put in their reports), never the shape; ``"gather"`` forces the
legacy path.  ``"pallas"``/``"xla"`` pin one flash implementation for
tests.

Exact-current-token semantics are preserved: the int8 *decode* paths
overlay the in-flight token's exact f32 K/V (storage is quantized, the
attended view is exact — the model's ``_write_kv`` contract), folded at
score / context granularity here; chunked prefill deliberately does NOT
overlay (per-token quantization keeps prefill chunk-alignment-invariant,
the prefix-cache bit-identity property).  Speculative verify is f32-only
upstream, so its flash path is the bitwise-identical f32 form.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.parallel import sharding as _layout

NEG_BIG = -1e30  # finite mask fill, matching the gather reference

KERNELS = ("auto", "flash", "gather", "pallas", "xla")


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_kernel(kernel: str) -> str:
    """Normalize a ``--decode-kernel`` choice to ``"flash"``/``"gather"``
    (the two *semantic* paths; ``"pallas"``/``"xla"`` pin a flash
    implementation and resolve to themselves for tests)."""
    if kernel not in KERNELS:
        raise ValueError(
            f"unknown decode kernel {kernel!r} (choices: {KERNELS})"
        )
    return "flash" if kernel == "auto" else kernel


def flash_impl(kernel: str) -> str:
    """What a resolved kernel actually runs HERE — ``"pallas"``,
    ``"xla"`` or ``"gather"``: ``flash`` is the Pallas kernel on TPU and
    the fused-XLA twin elsewhere; explicit ``pallas``/``xla`` force one
    (tests; the Pallas path interprets off-TPU).  The engines report
    this, so a run that asked for the kernel and got the twin says so."""
    kernel = resolve_kernel(kernel)
    if kernel == "flash":
        return "xla" if _use_interpret() else "pallas"
    return kernel


def _sqrt_dim(hd: int):
    # the score DIVISOR: the gather reference divides by jnp.sqrt(hd);
    # keep the exact same op so the f32 twin stays bitwise identical
    return jnp.sqrt(jnp.asarray(hd, jnp.float32))


# --------------------------------------------------------------------------
# Pallas kernel: online-softmax split-K over block-table pages
# --------------------------------------------------------------------------


def _fold_block(s, v, m_ref, l_ref, acc_ref, at=()):
    """Fold one history block into the running softmax kept in scratch:
    masked scores ``s`` [rows, block] and values ``v`` [block, dv] update
    the maximum ``m``, the denominator ``l`` and the weighted sum ``acc``
    at index ``at`` of their refs (one head of the multi-head kernel; the
    whole of the grouped-query one).  Both kernels' one softmax body."""
    col = (*at, slice(None), slice(0, 1))
    m_prev = m_ref[col]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    corr = jnp.exp(m_prev - m_cur)
    l_ref[col] = l_ref[col] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[(*at, ...)] = acc_ref[(*at, ...)] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[col] = m_cur


def _kernel(tables_ref, maxpos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            ko_ref, vo_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block: int, num_heads: int, hd: int, quantized: bool,
            overlay: bool):
    """One (slot, history-block) grid step over ALL heads.

    Every block covers its array's trailing dims whole — the Mosaic block
    rule (last two block dims divisible by the dtype tile or equal to the
    array dims) holds for any ``h``/``hd``, so ``hd=64 < 128`` lanes and
    ``h=12`` need no padding:

    ``q_ref``/``o_ref`` [1, h, nq, hd] (head-major, so a head is a
    leading-dim index); ``k_ref``/``v_ref`` [1, block, h * hd] — the
    physical page the index_map resolved through the prefetched block
    table, its heads folded into the minor axis as the pool holds them
    (``serve.kv_cache.init_paged_cache``), one head read per loop step as
    the static lane slice ``[hh * hd, (hh + 1) * hd)``;
    ``ks_ref``/``vs_ref`` [1, block, h] per-(position, head) scales (int8
    pools); ``ko_ref``/``vo_ref`` [1, h, hd] the slot's exact in-flight
    token (decode overlay); ``pos_ref`` [1, nq, 1] the per-query
    positions as a VMEM vector.  SMEM carries scalars only: the block
    tables (index_map) and ``maxpos_ref`` [b], the block-skip bound.
    Scratch ``m``/``l`` [h, nq, 128] and ``acc`` [h, nq, hd] carry the
    online-softmax state across the sequential history dimension.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    maxpos = maxpos_ref[b]  # scalar (SMEM): newest visible position

    # whole-block skip past the newest visible position: blocks beyond
    # max(posmat) contribute nothing (the split-K causal saving)
    @pl.when(j * block <= maxpos)
    def _compute():
        pos = pos_ref[0]  # [nq, 1] int32
        nq = pos.shape[0]
        cols = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (nq, block), 1
        )  # logical positions of this tile, per query row
        visible = cols <= pos  # [nq, block]
        if overlay:
            # decode's exact-current-token contract: the attended view
            # holds the in-flight f32 K/V at the slot's own position
            # (nq == 1, so that position IS the skip bound)
            own = (
                j * block
                + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            ) == maxpos  # [block, 1]
        for hh in range(num_heads):
            q = q_ref[0, hh]  # [nq, hd]
            lanes = slice(hh * hd, (hh + 1) * hd)  # head hh of the page
            kf = k_ref[0, :, lanes].astype(jnp.float32)  # [block, hd]
            vf = v_ref[0, :, lanes].astype(jnp.float32)
            if quantized:
                # in-tile dequant: one multiply per stored vector at
                # [block, hd] granularity — f32 history never leaves VMEM
                kf = kf * ks_ref[0, :, hh:hh + 1]
                vf = vf * vs_ref[0, :, hh:hh + 1]
            if overlay:
                kf = jnp.where(own, ko_ref[0, hh:hh + 1, :], kf)
                vf = jnp.where(own, vo_ref[0, hh:hh + 1, :], vf)
            s = jax.lax.dot_general(
                q, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) / math.sqrt(hd)  # [nq, block]
            s = jnp.where(visible, s, NEG_BIG)
            _fold_block(s, vf, m_ref, l_ref, acc_ref, at=(hh,))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        for hh in range(num_heads):
            l = jnp.maximum(l_ref[hh, :, :1], 1e-30)
            o_ref[0, hh] = (acc_ref[hh] / l).astype(o_ref.dtype)


def _kernel_decode(tables_ref, maxpos_ref, q_ref, k_ref, v_ref, o_ref,
                   qbd_ref, m_ref, l_ref, acc_ref, *, block: int,
                   num_heads: int, hd: int):
    """One (slot, history-block) grid step of the one-row float32 decode:
    ALL heads in one matmul a page.

    ``q_ref`` [1, 1, h * hd] is the slot's query as one row, heads side by
    side as the pool folds them.  At the slot's first step it is laid out
    block-diagonally in ``qbd_ref`` [h, h * hd] (row ``hh`` carries head
    ``hh`` in lanes ``[hh * hd, (hh + 1) * hd)``, zeros elsewhere), so one
    dot against the folded page ``k_ref`` [1, block, h * hd] gives every
    head's scores [h, block], one softmax fold runs over them, and one dot
    with ``v_ref`` [1, block, h * hd] adds into ``acc`` [h, h * hd], whose
    diagonal blocks are the heads' contexts.  The zeros add exact zeros, so
    each head's sums are the ones :func:`_kernel`'s per-head dots make.
    ``o_ref`` [1, 1, h * hd], laid out as ``q_ref``; the row sits at the
    slot's position ``maxpos_ref[b]``, which is also the block-skip bound."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        rows = jax.lax.broadcasted_iota(jnp.int32, qbd_ref.shape, 0) * hd
        lanes = jax.lax.broadcasted_iota(jnp.int32, qbd_ref.shape, 1)
        own = (lanes >= rows) & (lanes < rows + hd)
        qbd_ref[...] = jnp.where(own, q_ref[0], 0.0)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    maxpos = maxpos_ref[b]

    @pl.when(j * block <= maxpos)
    def _compute():
        s = jax.lax.dot_general(
            qbd_ref[...], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / math.sqrt(hd)  # [h, block]
        cols = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= maxpos, s, NEG_BIG)
        _fold_block(s, v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        for hh in range(num_heads):  # once a slot: each head's own block
            lanes = slice(hh * hd, (hh + 1) * hd)
            l = jnp.maximum(l_ref[hh:hh + 1, :1], 1e-30)
            o_ref[0, :, lanes] = (acc_ref[hh:hh + 1, lanes] / l).astype(
                o_ref.dtype)


def _page_map(block: int):
    """The index map of a pool page (and its scales) for grid step ``(bb,
    j)``: blocks past the slot's newest position ``mp[bb]`` repeat its last
    page, so they fetch nothing, as they compute nothing."""

    def page(bb, j, tbl, mp):
        return (tbl[bb, jnp.minimum(j, mp[bb] // block)], 0, 0)

    return page


def _compiler_params():
    if _use_interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _pallas_decode_f32(q, k_l, v_l, tables, pos, *, block: int):
    """The one-row float32 form: ``q`` [b, h, hd] at ``pos`` [b] against the
    folded pages through ``tables`` [b, nb].  Returns [b, h, hd] f32."""
    b, h, hd = q.shape
    row = pl.BlockSpec((1, 1, h * hd), lambda bb, j, tbl, mp: (bb, 0, 0))
    page = _page_map(block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, tables.shape[1]),
        in_specs=[
            row,
            pl.BlockSpec((1, block, h * hd), page),
            pl.BlockSpec((1, block, h * hd), page),
        ],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, h * hd), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, h * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_decode, block=block, num_heads=h, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * hd), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=_kernel_name(1, False, False),
    )(tables, pos, q.reshape(b, 1, h * hd), k_l, v_l)
    return out.reshape(b, h, hd)


def _kernel_name(nq: int, quantized: bool, overlay: bool) -> str:
    """Stable ``pallas_call`` name per kernel form, so a trace reduction
    finds the decode / chunk-prefill / verify kernels after a refactor."""
    form = "decode" if nq == 1 else "multiquery"
    pool = "int8" if quantized else "f32"
    return f"flash_decode_{form}_{pool}" + ("_overlay" if overlay else "")


def _pallas_attention(
    q4: jax.Array,
    k_l: jax.Array,
    v_l: jax.Array,
    k_s: Optional[jax.Array],
    v_s: Optional[jax.Array],
    tables: jax.Array,
    posmat: jax.Array,
    *,
    block: int,
    k_own: Optional[jax.Array] = None,
    v_own: Optional[jax.Array] = None,
) -> jax.Array:
    """The kernel call: ``q4`` [b, nq, h, hd] against pool pages ``k_l``/
    ``v_l`` [P, block, h * hd] (heads folded, ``h`` and ``hd`` taken from
    ``q4``; scales ``k_s``/``v_s`` [P, block, h]) addressed through
    ``tables`` [b, nb]; ``posmat`` [b, nq] per-query visibility.  Returns
    [b, nq, h, hd] f32.  The one-row float32 form (decode) runs
    :func:`_kernel_decode`, all heads in one matmul a page; the others
    (chunk prefill and verify, whose 64 rows would make that 32 times the
    multiply-adds, and the int8 pool with its overlay) run :func:`_kernel`,
    a head at a time.
    """
    b, nq, h, hd = q4.shape
    nb = tables.shape[1]
    quantized = k_s is not None
    overlay = k_own is not None
    posmat = posmat.astype(jnp.int32)
    if nq == 1 and not quantized and not overlay:
        return _pallas_decode_f32(
            q4[:, 0], k_l, v_l, tables, posmat[:, 0], block=block
        )[:, None]
    if overlay and nq != 1:
        # the in-kernel own-position select reads the slot's single
        # position — the single-token decode contract; a multi-query
        # overlay would silently place every row's overlay at one position
        raise ValueError(
            "own-token overlay supports single-query decode only "
            f"(nq={nq})"
        )
    kern = functools.partial(
        _kernel, block=block, num_heads=h, hd=hd, quantized=quantized,
        overlay=overlay,
    )
    # unquantized/no-overlay variants still take the operand slots (one
    # kernel signature); size-1 dummies keep the BlockSpecs trivial
    dummy_s = jnp.zeros((1, 1, 1), jnp.float32)
    dummy_o = jnp.zeros((1, 1, hd), jnp.float32)
    head_major = pl.BlockSpec(
        (1, h, nq, hd), lambda bb, j, tbl, mp: (bb, 0, 0, 0)
    )
    page_spec = pl.BlockSpec((1, block, h * hd), _page_map(block))
    if quantized:
        scale_spec = pl.BlockSpec((1, block, h), _page_map(block))
    else:
        scale_spec = pl.BlockSpec(
            (1, 1, 1), lambda bb, j, tbl, mp: (0, 0, 0)
        )
    if overlay:
        own_spec = pl.BlockSpec(
            (1, h, hd), lambda bb, j, tbl, mp: (bb, 0, 0)
        )
    else:
        own_spec = pl.BlockSpec(
            (1, 1, hd), lambda bb, j, tbl, mp: (0, 0, 0)
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables + skip bound land in SMEM up front
        grid=(b, nb),
        in_specs=[
            head_major,
            page_spec,
            page_spec,
            scale_spec,
            scale_spec,
            own_spec,
            own_spec,
            pl.BlockSpec((1, nq, 1), lambda bb, j, tbl, mp: (bb, 0, 0)),
        ],
        out_specs=head_major,
        scratch_shapes=[
            pltpu.VMEM((h, nq, 128), jnp.float32),
            pltpu.VMEM((h, nq, 128), jnp.float32),
            pltpu.VMEM((h, nq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, nq, hd), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=_kernel_name(nq, quantized, overlay),
    )(
        tables,
        posmat.max(axis=1),
        jnp.swapaxes(q4, 1, 2),
        k_l,
        v_l,
        k_s if quantized else dummy_s,
        v_s if quantized else dummy_s,
        k_own if overlay else dummy_o,
        v_own if overlay else dummy_o,
        posmat[:, :, None],
    )
    return jnp.swapaxes(out, 1, 2)


def attention_partition_specs(operands, *, mesh, namespace: str = "attn"):
    """PartitionSpecs for the Pallas kernel's operands under a mesh,
    resolved through the partition-rule layout table (the ``attn/`` and
    ``attn_dense/`` rules in ``parallel.sharding.LAYOUT_RULES``) — the
    kernel's block-spec partitioning never hand-wires a mesh axis.
    ``operands``: name → array (None entries are absent kernel slots and
    are skipped).  Returns ``(names, in_specs, out_spec)``; size-1 dummy
    operands replicate via the table's divisibility drop."""
    names = [k for k, v in operands.items() if v is not None]
    in_specs = tuple(
        _layout.spec_for(
            f"{namespace}/{k}", shape=tuple(operands[k].shape), mesh=mesh
        )
        for k in names
    )
    out_spec = _layout.spec_for(
        f"{namespace}/out", shape=tuple(operands["q"].shape), mesh=mesh
    )
    return names, in_specs, out_spec


def _per_shard(mesh, operands, run, *, namespace: str):
    """Call ``run(**operands)`` per shard: a bare ``pallas_call`` cannot be
    partitioned by GSPMD (it would gather the global operands onto every
    chip or be refused), so under a multi-device mesh the kernel runs
    inside ``shard_map`` with operand partitioning resolved through the
    layout table — heads over ``tensor`` (independent in attention, so no
    collective), dense-layout slots over the data axes."""
    if mesh is None or mesh.devices.size == 1:
        return run(**operands)
    from distributeddeeplearning_tpu.parallel.compat import shard_map

    names, in_specs, out_spec = attention_partition_specs(
        operands, mesh=mesh, namespace=namespace
    )
    absent = {k: None for k in operands if k not in names}
    return shard_map(
        lambda *present: run(**dict(zip(names, present)), **absent),
        mesh=mesh, in_specs=in_specs, out_specs=out_spec,
    )(*(operands[k] for k in names))


def _pallas_paged(mesh, q4, k_l, v_l, k_s, v_s, tables, posmat, *, block,
                  k_own=None, v_own=None):
    """The kernel over pool pages, per shard under a mesh: each chip runs
    its LOCAL heads (the paged pool never shards its page axis, so page
    addressing stays chip-local by construction)."""

    def run(q, k_pages, v_pages, k_scale, v_scale, tables, posmat, k_own,
            v_own):
        return _pallas_attention(
            q, k_pages, v_pages, k_scale, v_scale, tables, posmat,
            block=block, k_own=k_own, v_own=v_own,
        )

    return _per_shard(mesh, {
        "q": q4, "k_pages": k_l, "v_pages": v_l,
        "k_scale": k_s, "v_scale": v_s,
        "tables": tables, "posmat": posmat,
        "k_own": k_own, "v_own": v_own,
    }, run, namespace="attn")


def _pallas_dense(mesh, q4, k_l, v_l, k_s, v_s, posmat, *, k_own=None,
                  v_own=None):
    """The kernel over the dense [B, S, h, hd] layout, per shard under a
    mesh: each chip views ITS slots' rows as synthetic pages (the identity
    block tables are built per shard, so they index local rows)."""
    block = dense_block(k_l.shape[1])

    def run(q, k_rows, v_rows, k_scale, v_scale, posmat, k_own, v_own):
        kp, vp, ksp, vsp, tables = _dense_as_pages(
            k_rows, v_rows, k_scale, v_scale, block
        )
        return _pallas_attention(
            q, kp, vp, ksp, vsp, tables, posmat, block=block,
            k_own=k_own, v_own=v_own,
        )

    return _per_shard(mesh, {
        "q": q4, "k_rows": k_l, "v_rows": v_l,
        "k_scale": k_s, "v_scale": v_s, "posmat": posmat,
        "k_own": k_own, "v_own": v_own,
    }, run, namespace="attn_dense")


def dense_block(s: int, cap: int = 128) -> int:
    """The synthetic "page size" the dense layout tiles its [B, S] rows
    into for the kernel: ``s`` itself up to ``cap``, else the largest
    sublane-aligned (multiple of 8) divisor of ``s`` up to ``cap``.  A
    length with no such divisor would run a pathological grid, and is
    refused here rather than rerouted."""
    if s <= cap:
        return s
    for b in range(cap, 7, -8):
        if s % b == 0:
            return b
    raise ValueError(
        f"dense cache length {s} has no multiple-of-8 divisor up to {cap} "
        "for the flash-decode kernel to tile it by — pick a max_seq that "
        "is a multiple of 8 (128 tiles best), or the paged layout"
    )


def _dense_as_pages(k_l, v_l, k_s, v_s, block: int):
    """View a dense cache layer (values [B, S, h, hd], scales [B, S, h]) as
    pool pages [B·S/block, block, h * hd] and [B·S/block, block, h] plus the
    identity block tables: a reshape of its rows, so the kernel's paged
    addressing covers the dense layout.  (Row-major the reshape moves
    nothing; where the device keeps ``(h, hd)`` in padded tiles it is a copy
    of the layer: the dense cache is not folded yet.)"""
    b, s = k_l.shape[0], k_l.shape[1]
    nb = s // block

    def pages(leaf):
        if leaf is None:
            return None
        return leaf.reshape(b * nb, block, -1)

    tables = (
        jnp.arange(b, dtype=jnp.int32)[:, None] * nb
        + jnp.arange(nb, dtype=jnp.int32)[None]
    )
    return pages(k_l), pages(v_l), pages(k_s), pages(v_s), tables


# --------------------------------------------------------------------------
# Fused-XLA twin: scale-folded int8, verbatim-legacy f32
# --------------------------------------------------------------------------


def _xla_int8_scores(q3, kf, k_sc_t, hd):
    """Folded scores: ``(q · k_int8f32) * scale`` — the per-position
    scale multiplies the [b, h, s] score vector, never the [b, s, h, hd]
    history."""
    raw = jnp.einsum("bhd,bshd->bhs", q3, kf)
    return raw * k_sc_t / _sqrt_dim(hd)


def _xla_int8_decode(q3, kf, vf, k_sc_t, v_sc_t, k_t, v_t, pos, s, hd):
    """Scale-folded int8 decode attention over converted values ``kf``/
    ``vf`` [b, s, h, hd] (bare int8→f32 widening — the one history-sized
    f32 the fused program keeps) with scales transposed to [b, h, s].
    The exact-own-token contract folds too: the slot's own position gets
    its score from the in-flight f32 K and its context contribution from
    the in-flight f32 V — O(b·h) extras, not an O(b·s·h·hd) select."""
    scores = _xla_int8_scores(q3, kf, k_sc_t, hd)
    own_score = jnp.einsum("bhd,bhd->bh", q3, k_t) / _sqrt_dim(hd)
    own = jnp.arange(s)[None, None, :] == pos[:, None, None]  # [b, 1, s]
    scores = jnp.where(own, own_score[..., None], scores)
    visible = jnp.arange(s)[None, :] <= pos[:, None]
    scores = jnp.where(visible[:, None, :], scores, NEG_BIG)
    attn = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(own, 0.0, attn * v_sc_t)
    ctx = jnp.einsum("bhs,bshd->bhd", w, vf)
    attn_own = jnp.take_along_axis(attn, pos[:, None, None], axis=-1)[..., 0]
    return ctx + attn_own[..., None] * v_t


# --------------------------------------------------------------------------
# call-site entry points (one per consumer, shapes preserved exactly so
# the f32 gather/XLA paths stay bitwise identical to the legacy inline
# code they were moved from)
# --------------------------------------------------------------------------


def decode_attention_paged(
    q3, k_l, v_l, k_s, v_s, k_t, v_t, pos, block_tables, *,
    page_size: int, kernel: str = "gather", mesh=None,
):
    """Single-token decode attention over the paged pool.

    ``q3``/``k_t``/``v_t``: [b, h, hd] (query + the exact in-flight
    token); ``k_l``/``v_l``: [P, ps, h * hd] (this layer's pool rows, heads
    folded into the minor axis, already holding the current token's
    quantized write); ``k_s``/``v_s``: [P, ps, h] f32 or None; ``pos``:
    [b]; returns ctx [b, h, hd].  The kernel reads a head as a lane slice
    of the page; the other paths split the heads after the table gather.
    """
    b, num_heads, hd = q3.shape
    nb = block_tables.shape[1]
    s = nb * page_size
    impl = flash_impl(kernel)
    if impl != "gather":
        if impl == "pallas":
            out = _pallas_paged(
                mesh, q3[:, None], k_l, v_l, k_s, v_s, block_tables,
                pos[:, None], block=page_size,
                k_own=k_t if k_s is not None else None,
                v_own=v_t if k_s is not None else None,
            )
            return out[:, 0]
        if k_s is None:
            # f32 flash-XLA == the gather reference, op for op: there is
            # no dequant to fuse, and keeping the identical program is
            # what extends the decode==full-forward bitwise pin
            return _gather_decode_paged(
                q3, k_l, v_l, None, None, k_t, v_t, pos, block_tables,
                page_size=page_size,
            )
        kf = k_l[block_tables].reshape(b, s, num_heads, hd).astype(
            jnp.float32
        )
        vf = v_l[block_tables].reshape(b, s, num_heads, hd).astype(
            jnp.float32
        )
        k_sc_t = jnp.swapaxes(k_s[block_tables].reshape(b, s, num_heads), 1, 2)
        v_sc_t = jnp.swapaxes(v_s[block_tables].reshape(b, s, num_heads), 1, 2)
        return _xla_int8_decode(
            q3, kf, vf, k_sc_t, v_sc_t, k_t, v_t, pos, s, hd
        )
    return _gather_decode_paged(
        q3, k_l, v_l, k_s, v_s, k_t, v_t, pos, block_tables,
        page_size=page_size,
    )


def _gather_decode_paged(
    q3, k_l, v_l, k_s, v_s, k_t, v_t, pos, block_tables, *, page_size: int
):
    """Legacy paged decode attention (``forward_decode_paged``'s
    contract): block-table gather reconstructing the dense
    [b, s, h, hd] view, dequant + own-token select at history granularity
    on int8 pools — the reference the flash paths are pinned against."""
    from distributeddeeplearning_tpu.quant.qtensor import dequantize_kv

    b, num_heads, hd = q3.shape
    nb = block_tables.shape[1]
    s = nb * page_size
    if k_s is not None:
        own = (jnp.arange(s)[None, :] == pos[:, None])[..., None, None]
        k_seq = jnp.where(
            own,
            k_t[:, None],
            dequantize_kv(
                k_l[block_tables].reshape(b, s, num_heads, hd),
                k_s[block_tables].reshape(b, s, num_heads),
            ),
        )
        v_seq = jnp.where(
            own,
            v_t[:, None],
            dequantize_kv(
                v_l[block_tables].reshape(b, s, num_heads, hd),
                v_s[block_tables].reshape(b, s, num_heads),
            ),
        )
    else:
        k_seq = k_l[block_tables].reshape(b, s, num_heads, hd)
        v_seq = v_l[block_tables].reshape(b, s, num_heads, hd)
    scores = jnp.einsum("bhd,bshd->bhs", q3, k_seq) / _sqrt_dim(hd)
    visible = jnp.arange(s)[None, :] <= pos[:, None]  # [b, s]
    scores = jnp.where(visible[:, None, :], scores, NEG_BIG)
    attn = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
    return jnp.einsum("bhs,bshd->bhd", attn, v_seq)


def decode_attention_dense(
    q3, k_l, v_l, k_s, v_s, k_t, v_t, pos, *, kernel: str = "gather",
    mesh=None,
):
    """Single-token decode attention over the dense [b, S, h, hd] layout
    (same contract as :func:`decode_attention_paged`, no indirection)."""
    b, num_heads, hd = q3.shape
    s = k_l.shape[1]
    impl = flash_impl(kernel)
    if impl != "gather":
        if impl == "pallas":
            out = _pallas_dense(
                mesh, q3[:, None], k_l, v_l, k_s, v_s, pos[:, None],
                k_own=k_t if k_s is not None else None,
                v_own=v_t if k_s is not None else None,
            )
            return out[:, 0]
        if k_s is None:
            return _gather_decode_dense(
                q3, k_l, v_l, None, None, k_t, v_t, pos
            )
        kf = k_l.astype(jnp.float32)
        vf = v_l.astype(jnp.float32)
        k_sc_t = jnp.swapaxes(k_s, 1, 2)
        v_sc_t = jnp.swapaxes(v_s, 1, 2)
        return _xla_int8_decode(
            q3, kf, vf, k_sc_t, v_sc_t, k_t, v_t, pos, s, hd
        )
    return _gather_decode_dense(q3, k_l, v_l, k_s, v_s, k_t, v_t, pos)


def _gather_decode_dense(q3, k_l, v_l, k_s, v_s, k_t, v_t, pos):
    """Legacy dense decode attention (``forward_decode``'s contract)."""
    from distributeddeeplearning_tpu.quant.qtensor import dequantize_kv

    b, num_heads, hd = q3.shape
    s = k_l.shape[1]
    if k_s is not None:
        own = (jnp.arange(s)[None, :] == pos[:, None])[..., None, None]
        k_seq = jnp.where(own, k_t[:, None], dequantize_kv(k_l, k_s))
        v_seq = jnp.where(own, v_t[:, None], dequantize_kv(v_l, v_s))
    else:
        k_seq, v_seq = k_l, v_l
    scores = jnp.einsum("bhd,bshd->bhs", q3, k_seq) / _sqrt_dim(hd)
    visible = jnp.arange(s)[None, :] <= pos[:, None]
    scores = jnp.where(visible[:, None, :], scores, NEG_BIG)
    attn = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
    return jnp.einsum("bhs,bshd->bhd", attn, v_seq)


def chunk_attention(
    q_c, k_l, v_l, k_s, v_s, block_table, posns, *,
    page_size: int, kernel: str = "gather", mesh=None,
):
    """Chunked-prefill history attention: ``q_c`` [C, h, hd] at logical
    positions ``posns`` [C] against ONE sequence's pages (``block_table``
    [nb]).  No own-token overlay on int8 pools — prefill attends the
    cache-roundtripped values so quantized prefill stays chunk-alignment-
    invariant (``forward_prefill_chunk``'s prefix-cache contract).
    Returns ctx [C, h, hd]."""
    C, num_heads, hd = q_c.shape
    nb = block_table.shape[0]
    s = nb * page_size
    impl = flash_impl(kernel)
    if impl != "gather":
        if impl == "pallas":
            out = _pallas_paged(
                mesh, q_c[None], k_l, v_l, k_s, v_s, block_table[None],
                posns[None], block=page_size,
            )
            return out[0]
        if k_s is None:
            return _gather_chunk(
                q_c, k_l, v_l, None, None, block_table, posns,
                page_size=page_size,
            )
        kf = k_l[block_table].reshape(s, num_heads, hd).astype(jnp.float32)
        vf = v_l[block_table].reshape(s, num_heads, hd).astype(jnp.float32)
        k_sc_t = jnp.swapaxes(k_s[block_table].reshape(s, num_heads), 0, 1)
        v_sc_t = jnp.swapaxes(v_s[block_table].reshape(s, num_heads), 0, 1)
        raw = jnp.einsum("chd,shd->chs", q_c, kf)
        scores = raw * k_sc_t[None] / _sqrt_dim(hd)
        visible = jnp.arange(s)[None, :] <= posns[:, None]  # [C, s]
        scores = jnp.where(visible[:, None, :], scores, NEG_BIG)
        attn = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("chs,shd->chd", attn * v_sc_t[None], vf)
    return _gather_chunk(
        q_c, k_l, v_l, k_s, v_s, block_table, posns, page_size=page_size
    )


def _gather_chunk(
    q_c, k_l, v_l, k_s, v_s, block_table, posns, *, page_size: int
):
    """Legacy chunk attention (``forward_prefill_chunk``'s contract)."""
    from distributeddeeplearning_tpu.quant.qtensor import dequantize_kv

    C, num_heads, hd = q_c.shape
    nb = block_table.shape[0]
    s = nb * page_size
    if k_s is not None:
        k_seq = dequantize_kv(
            k_l[block_table].reshape(s, num_heads, hd),
            k_s[block_table].reshape(s, num_heads),
        )
        v_seq = dequantize_kv(
            v_l[block_table].reshape(s, num_heads, hd),
            v_s[block_table].reshape(s, num_heads),
        )
    else:
        k_seq = k_l[block_table].reshape(s, num_heads, hd)
        v_seq = v_l[block_table].reshape(s, num_heads, hd)
    scores = jnp.einsum("chd,shd->chs", q_c, k_seq) / _sqrt_dim(hd)
    visible = jnp.arange(s)[None, :] <= posns[:, None]  # [C, s]
    scores = jnp.where(visible[:, None, :], scores, NEG_BIG)
    attn = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
    return jnp.einsum("chs,shd->chd", attn, v_seq)


def verify_attention_paged(
    q4, k_l, v_l, block_tables, posmat, *, page_size: int,
    kernel: str = "gather", mesh=None,
):
    """Speculative-verify attention over the paged pool: ``q4``
    [b, K1, h, hd] with per-query positions ``posmat`` [b, K1].  f32
    pools only (the verify programs refuse int8 upstream), so the flash
    XLA twin IS the gather reference — the spec bitwise pin rides
    through unchanged; on TPU the Pallas kernel streams the same pages
    the decode step does.  Returns ctx [b, K1, h, hd]."""
    b, K1, num_heads, hd = q4.shape
    if flash_impl(kernel) == "pallas":
        return _pallas_paged(
            mesh, q4, k_l, v_l, None, None, block_tables, posmat,
            block=page_size,
        )
    nb = block_tables.shape[1]
    s = nb * page_size
    k_seq = k_l[block_tables].reshape(b, s, num_heads, hd)
    v_seq = v_l[block_tables].reshape(b, s, num_heads, hd)
    return _verify_dense_math(q4, k_seq, v_seq, posmat, hd)


def verify_attention_dense(q4, k_l, v_l, posmat, *, kernel: str = "gather",
                           mesh=None):
    """Speculative-verify attention over the dense cache ``k_l``/``v_l``
    [b, S, h, hd] (f32 only, see :func:`verify_attention_paged`)."""
    if flash_impl(kernel) == "pallas":
        return _pallas_dense(mesh, q4, k_l, v_l, None, None, posmat)
    return _verify_dense_math(q4, k_l, v_l, posmat, q4.shape[-1])


def _verify_dense_math(q4, k_seq, v_seq, posmat, hd):
    """The verify einsums (``forward_verify``'s contract)."""
    s = k_seq.shape[1]
    scores = jnp.einsum("bqhd,bshd->bqhs", q4, k_seq) / _sqrt_dim(hd)
    visible = jnp.arange(s)[None, None, :] <= posmat[:, :, None]
    scores = jnp.where(visible[:, :, None, :], scores, NEG_BIG)
    attn = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
    return jnp.einsum("bqhs,bshd->bqhd", attn, v_seq)


# --------------------------------------------------------------------------
# Grouped-query attention whose keys and values differ in width, over a
# pool whose KV heads are folded into the minor axis: ``k`` pages [P, block,
# kv_heads * dk], ``v`` pages [P, block, kv_heads * dv] (a bfloat16 page
# tiles without padding whatever ``kv_heads`` is).  The model side is
# ``models.hybrid_moe_transformer``; nothing above this line runs for it and
# nothing below runs for the multi-head models above.
# --------------------------------------------------------------------------


def gqa_attend(q, keys, vals, visible, sink=None):
    """Plain grouped-query attention: ``q`` [T, Hq, dk] over ``keys`` [S,
    Hkv, dk] / ``vals`` [S, Hkv, dv]; query head ``h`` reads KV head ``h //
    (Hq / Hkv)``; ``visible`` [T, S] says which keys a query sees.
    ``sink`` [Hq]: a learned logit per head that joins the softmax as one
    more column and is then dropped, so it adds ``exp(sink)`` to the
    denominator and nothing else.  Returns [T, Hq, dv] float32."""
    T, hq, dk = q.shape
    hkv = keys.shape[1]
    qg = q.reshape(T, hkv, hq // hkv, dk)
    s = jnp.einsum("thgd,shd->hgts", qg, keys,
                   preferred_element_type=jnp.float32) / math.sqrt(dk)
    s = jnp.where(visible[None, None], s, NEG_BIG)
    m = s.max(-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(hkv, hq // hkv, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(s - m)
    denom = p.sum(-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sk - m)
    p = (p / denom).astype(vals.dtype)
    out = jnp.einsum("hgts,shd->thgd", p, vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(T, hq, vals.shape[-1])


def _kernel_gqa(tables_ref, maxpos_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                l_ref, acc_ref, *, block: int, scale: float):
    """One (slot, history-block) grid step over ALL query heads at once.

    ``q_ref`` [1, Hq, kv_heads * dk] holds the slot's query heads
    block-diagonally: row ``h`` carries its query in the columns of the KV
    head it reads and zeros elsewhere, so ONE matmul against the folded
    page ``k_ref`` [1, block, kv_heads * dk] gives every head's scores
    [Hq, block] with no lane slicing of a 192-wide head out of the page.
    ``v_ref`` [1, block, kv_heads * dv]; the accumulator [Hq, kv_heads * dv]
    holds every KV head's values for every row, and the caller keeps each
    row's own head (a 128-aligned slice).  The extra multiply-adds are
    ``kv_heads`` times the needed ones and still far below the page's
    read time.  All rows sit at the slot's one position, ``maxpos_ref[b]``,
    which is also the block-skip bound."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    maxpos = maxpos_ref[b]

    @pl.when(j * block <= maxpos)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Hq, block]
        cols = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= maxpos, s, NEG_BIG)
        _fold_block(s, v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pallas_gqa_paged(q, k_pool, v_pool, pos, tables, *, block: int):
    """The GQA kernel call: ``q`` [B, Hq, dk] at ``pos`` [B] against the
    folded pools through ``tables`` [B, nb].  Returns [B, Hq, dv] f32."""
    B, hq, dk = q.shape
    hkv = k_pool.shape[-1] // dk
    dv = v_pool.shape[-1] // hkv
    g = hq // hkv
    nb = tables.shape[1]
    eye = jnp.eye(hkv, dtype=q.dtype)
    q_bd = (q.reshape(B, hkv, g, 1, dk) * eye[None, :, None, :, None]).reshape(
        B, hq, hkv * dk)
    pos = pos.astype(jnp.int32)

    def page(bb, j, tbl, mp):
        # blocks past the slot's newest position repeat its last page, so
        # they are skipped without a read of their own
        return (tbl[bb, jnp.minimum(j, mp[bb] // block)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, hq, hkv * dk), lambda bb, j, tbl, mp: (bb, 0, 0)),
            pl.BlockSpec((1, block, hkv * dk), page),
            pl.BlockSpec((1, block, hkv * dv), page),
        ],
        out_specs=pl.BlockSpec(
            (1, hq, hkv * dv), lambda bb, j, tbl, mp: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, hkv * dv), jnp.float32),
        ],
    )
    compiler_params = None
    if not _use_interpret():
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    out = pl.pallas_call(
        functools.partial(_kernel_gqa, block=block, scale=1.0 / math.sqrt(dk)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hq, hkv * dv), jnp.float32),
        compiler_params=compiler_params,
        interpret=_use_interpret(),
        name=f"flash_decode_decode_gqa_{jnp.dtype(k_pool.dtype).name}",
    )(tables, pos, q_bd, k_pool, v_pool)
    # each row keeps the values of the KV head it reads
    out = out.reshape(B, hkv, g, hkv, dv)
    return jnp.stack([out[:, h, :, h] for h in range(hkv)], axis=1).reshape(
        B, hq, dv)


def decode_attention_gqa_paged(
    q, k_pool, v_pool, pos, block_tables, *, page_size: int,
    kernel: str = "gather", sink=None,
):
    """Single-token grouped-query decode attention over the folded paged
    pool: ``q`` [B, Hq, dk] at ``pos`` [B]; ``k_pool`` [P, ps, Hkv * dk],
    ``v_pool`` [P, ps, Hkv * dv], already holding the current token's
    write.  The Pallas kernel streams each slot's pages up to its own
    position; every other choice gathers the block tables' whole history
    (the reference the kernel is pinned against, not a serving path at a
    long ``max_seq``).  Returns [B, Hq, dv] float32."""
    B, hq, dk = q.shape
    if flash_impl(kernel) == "pallas":
        if sink is not None:
            raise NotImplementedError(
                "the grouped-query decode kernel takes no attention sink")
        return _pallas_gqa_paged(q, k_pool, v_pool, pos, block_tables,
                                 block=page_size)
    hkv = k_pool.shape[-1] // dk
    s = block_tables.shape[1] * page_size
    keys = k_pool[block_tables].reshape(B, s, hkv, dk)
    vals = v_pool[block_tables].reshape(B, s, hkv, -1)
    visible = jnp.arange(s)[None, :] <= pos[:, None]
    return jax.vmap(
        lambda q1, k1, v1, vis: gqa_attend(q1[None], k1, v1, vis[None], sink)[0]
    )(q, keys, vals, visible)


#: pages of history a chunk's attention reads at a time
HISTORY_PAGES = 4


def chunk_attention_gqa_paged(
    q, k_pool, v_pool, block_table, posns, *, page_size: int, sink=None,
):
    """Chunked-prefill grouped-query attention of ``q`` [C, Hq, dk] at
    positions ``posns`` [C] (ascending) over ONE sequence's pages
    (``block_table`` [nb]), the chunk's own K/V already written.  The
    history is read :data:`HISTORY_PAGES` pages at a time with a running
    softmax, and only up to the chunk's last position: the cost follows
    the live context, not the block table's length, and no [C, max_seq]
    score matrix exists.  Returns [C, Hq, dv] float32."""
    C, hq, dk = q.shape
    hkv = k_pool.shape[-1] // dk
    dv = v_pool.shape[-1] // hkv
    g = hq // hkv
    history_pages = HISTORY_PAGES
    kb = history_pages * page_size
    nb = block_table.shape[0]
    blocks_max = -(-nb // history_pages)
    table = jnp.pad(block_table, (0, blocks_max * history_pages - nb))
    n_blocks = jnp.minimum(posns[-1] // kb + 1, blocks_max)
    qg = q.reshape(C, hkv, g, dk)

    def body(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, i * history_pages,
                                           history_pages)
        keys = k_pool[ids].reshape(kb, hkv, dk)
        vals = v_pool[ids].reshape(kb, hkv, dv)
        s = jnp.einsum("chgd,khd->hgck", qg, keys,
                       preferred_element_type=jnp.float32) / math.sqrt(dk)
        kpos = i * kb + jnp.arange(kb)
        s = jnp.where(kpos[None, :] <= posns[:, None], s, NEG_BIG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "hgck,khd->hgcd", p.astype(vals.dtype), vals,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((hkv, g, C), -jnp.inf, jnp.float32),
            jnp.zeros((hkv, g, C), jnp.float32),
            jnp.zeros((hkv, g, C, dv), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(hkv, g, 1)
        m_new = jnp.maximum(m, sk)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.exp(sk - m_new)
        acc = acc * corr[..., None]
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(2, 0, 1, 3).reshape(C, hq, dv)
