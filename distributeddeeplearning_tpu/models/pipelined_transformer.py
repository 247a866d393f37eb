"""A pipeline-parallel transformer: ops.pipeline_apply wired into a model.

Demonstrates the full PP training path (not just the op): a stack of
identical pre-LN transformer blocks whose parameters are created STACKED on
a leading layer dim ``[L, ...]`` — the natural layout for both
``lax.scan``-over-layers (fast compiles) and pipeline parallelism (reshape
``[L, ...] → [S, L/S, ...]`` and shard stage-wise over the ``pipe`` axis).

Pure-function design (plain pytrees, no module framework): parameters are
a dict of stacked arrays, the block is a jnp function, so the same code
runs three ways:

- ``forward(params, tokens)`` — lax.scan over all L layers (single chip);
- ``forward_pipelined(params, tokens, mesh=..., num_microbatches=...)`` —
  GPipe over the mesh's ``pipe`` axis via :func:`ops.pipeline.pipeline_apply`,
  each stage scanning its L/S local layers;
- both are interchangeable inside ``jax.grad``/``jax.jit`` — the test suite
  pins forward and gradient equivalence.

The reference has no pipeline parallelism (Horovod DP only); this is the
model-level consumer of the framework's ``pipe`` mesh axis.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.ops import flash_decode as _fd
from distributeddeeplearning_tpu.quant.qtensor import (
    qmatmul as _mm,
    quantize_kv as _q_kv,
    quantized_cache,
)

PyTree = Any


def init_params(
    rng: jax.Array,
    *,
    num_layers: int,
    d_model: int,
    num_heads: int,
    d_ff: int,
    vocab_size: int,
    max_len: int = 512,
) -> Dict[str, jax.Array]:
    """Stacked-parameter pytree; block weights carry a leading [L] dim."""
    if d_model % num_heads:
        raise ValueError(f"d_model {d_model} not divisible by heads {num_heads}")
    keys = jax.random.split(rng, 7)
    s = 0.02
    L = num_layers

    def nrm(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * s

    return {
        "embed": nrm(keys[0], (vocab_size, d_model)),
        "pos": nrm(keys[1], (max_len, d_model)),
        "blocks": {
            "qkv": nrm(keys[2], (L, d_model, 3 * d_model)),
            "proj": nrm(keys[3], (L, d_model, d_model)),
            "w_in": nrm(keys[4], (L, d_model, d_ff)),
            "w_out": nrm(keys[5], (L, d_ff, d_model)),
            "ln1": jnp.ones((L, d_model), jnp.float32),
            "ln2": jnp.ones((L, d_model), jnp.float32),
        },
        "head": nrm(keys[6], (d_model, vocab_size)),
    }


def _layer_norm(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _block(p: Dict[str, jax.Array], x: jax.Array, *, num_heads: int, attend):
    """THE pre-LN transformer layer, under every forward of this module;
    ``p`` leaves are per-layer (no [L] dim).

    ``x`` is the residual stream with the model dim last: ``[B, d]``
    (decode), ``[C, d]`` (chunk), ``[B, K1, d]`` (verify) or ``[b, s, d]``
    (training, prefill); the layer does not look at which.  What differs
    between the forwards is how keys and values are addressed, and that is
    the callback's: ``attend(q, k, v) -> (ctx, aux)`` takes this layer's
    projections split to heads (``x.shape[:-1] + (num_heads, hd)``), writes
    whatever cache it owns, and returns the attended context in ``q``'s
    shape beside whatever its forward's scan collects per layer (the
    written cache leaves, the layer's ``(k, v)``, or None).

    Returns ``(x, aux)``: a ``lax.scan`` body's ``(carry, y)``.
    """
    d = x.shape[-1]
    heads = x.shape[:-1] + (num_heads, d // num_heads)

    h = _layer_norm(x, p["ln1"])
    q, k, v = jnp.split(_mm(h, p["qkv"]), 3, axis=-1)  # fused [..., 3d]
    ctx, aux = attend(q.reshape(heads), k.reshape(heads), v.reshape(heads))
    x = x + _mm(ctx.reshape(x.shape).astype(x.dtype), p["proj"])

    h = _layer_norm(x, p["ln2"])
    x = x + _mm(jax.nn.gelu(_mm(h, p["w_in"]), approximate=False), p["w_out"])
    return x, aux


def _causal_attention(attention: str, attention_fn, dtype):
    """How a whole sequence attends over itself (training, prefill):
    ``(q, k, v) -> ctx``, all ``[b, s, h, hd]``, for :func:`_block`.

    ``attention``: ``"dense"`` materializes the [b,h,s,s] score matrix with a
    tril mask; ``"flash"`` runs the causal Pallas kernel
    (``ops.flash_attention`` with ``causal=True``) — O(block²) memory and
    ~half the FLOPs, the long-context decoder path.  Both are exact.

    ``attention_fn`` overrides both: a ``(q, k, v, mask, *, dtype)``
    callable in ``[B, S, H, D]`` layout (the ``models.bert`` contract) that
    must enforce causality itself — bind
    ``ops.make_ring_attention(mesh, causal=True)`` or
    ``ops.make_ulysses_attention(mesh, causal=True)`` for the
    sequence-parallel decoder.  ``dtype`` is the residual stream's.
    """
    if attention_fn is not None:
        return lambda q, k, v: attention_fn(q, k, v, None, dtype=dtype)
    if attention == "flash":
        from distributeddeeplearning_tpu.ops.flash_attention import (
            flash_attention,
        )

        return lambda q, k, v: flash_attention(
            q, k, v, None, dtype=dtype, causal=True
        )
    if attention != "dense":
        raise ValueError(f"unknown attention {attention!r}")

    def dense(q, k, v):
        s, hd = q.shape[1], q.shape[-1]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [b, h, s, hd]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(hd, jnp.float32)
        )
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -1e30)
        # softmax in f32 (scores were promoted by the f32 scale), then back
        # to the stream dtype — without the cast a bf16 residual stream
        # would silently promote to f32 and break the scan-over-layers
        # carry contract.
        attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", attn, v).transpose(0, 2, 1, 3)

    return dense


def _stack_scan(
    blocks: PyTree,
    x: jax.Array,
    *,
    num_heads: int,
    attention: str = "dense",
    attention_fn=None,
    remat: bool = False,
    unroll: int = 1,
) -> jax.Array:
    """lax.scan over the stacked layer dim — one compiled block body.

    ``remat=True`` wraps the body in ``jax.checkpoint`` so backward
    recomputes each layer instead of saving its activations — activation
    memory O(1) in depth, the long-context enabler (seq-32k needs it: 12
    saved [S, d_ff] intermediates alone are 2.25 GB bf16 at S=32k).
    """
    causal = _causal_attention(attention, attention_fn, x.dtype)

    def body(carry, layer_params):
        return _block(
            layer_params, carry, num_heads=num_heads,
            attend=lambda q, k, v: (causal(q, k, v), None),
        )

    if remat:
        body = jax.checkpoint(body)
    # unroll > 1 trades compile time for removing scan-carry
    # dynamic-update-slice traffic from the backward (the per-layer grad
    # stacking); unroll=num_layers makes the layer loop fully static.
    out, _ = jax.lax.scan(body, x, blocks, unroll=unroll)
    return out


def _embed(params, tokens):
    max_len = params["pos"].shape[0]
    if tokens.shape[1] > max_len:
        raise ValueError(
            f"sequence length {tokens.shape[1]} exceeds max_len {max_len}"
        )
    x = params["embed"][tokens]  # [b, s, d]
    return x + params["pos"][: tokens.shape[1]][None]


def forward(
    params,
    tokens,
    *,
    num_heads: int,
    attention: str = "dense",
    attention_fn=None,
    remat: bool = False,
    unroll: int = 1,
) -> jax.Array:
    """Next-token logits [b, s, vocab] — sequential (scan over all layers).

    ``attention_fn`` (see :func:`_causal_attention`) plugs a causal
    sequence-parallel attention (ring / Ulysses) into every layer — the
    multi-chip long-context decoder path.  Sequential forward only: the
    SP ops shard_map over the mesh themselves, which cannot nest inside
    ``forward_pipelined``'s pipe-axis shard_map.

    ``remat=True`` rematerializes each layer in backward (see
    :func:`_stack_scan`).
    """
    x = _embed(params, tokens)
    x = _stack_scan(
        params["blocks"], x, num_heads=num_heads, attention=attention,
        attention_fn=attention_fn, remat=remat, unroll=unroll,
    )
    return _mm(x, params["head"])


def forward_prefill(
    params,
    tokens,
    *,
    num_heads: int,
    attention: str = "dense",
    attention_fn=None,
):
    """Prompt pass for the serving engine: logits AND per-layer K/V.

    Same math as :func:`forward` (the parity test pins it), but the layer
    scan also emits each layer's key/value projections so the caller can
    seed a KV cache and decode never recomputes the prompt — the prefill
    half of the prefill/decode split.

    Returns ``(logits [b, s, vocab], k, v)`` with k/v in the cache layout
    ``[b, L, s, h, hd]`` (``serve.kv_cache`` slot layout minus the slot
    padding).  ``attention="flash"`` runs the causal Pallas kernel for the
    prompt pass — the O(S²)-free long-prompt path; under a mesh the caller
    passes the per-shard form as ``attention_fn`` (see
    :func:`_causal_attention`), since a bare kernel cannot be partitioned.
    """
    x = _embed(params, tokens)
    causal = _causal_attention(attention, attention_fn, x.dtype)

    def body(carry, layer_params):
        return _block(
            layer_params, carry, num_heads=num_heads,
            attend=lambda q, k, v: (causal(q, k, v), (k, v)),
        )

    x, (k, v) = jax.lax.scan(body, x, params["blocks"])
    # scan stacks layer-major [L, b, s, h, hd]; the cache is slot-major
    return _mm(x, params["head"]), jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1)


def _embed_at(params, tokens, positions):
    """Token plus learned position embedding for the cached forwards, whose
    tokens sit at ``positions`` of their own (same shape as ``tokens``).
    Padding past the table (a final chunk, an invalid verify column) reads
    its last row: those outputs are garbage the caller ignores."""
    last = params["pos"].shape[0] - 1
    return params["embed"][tokens] + params["pos"][jnp.minimum(positions, last)]


def _write_kv(leaves, at, k, v):
    """The K/V write of the cached forwards: the new tokens' ``k``/``v``
    (``[..., h, hd]``) into this layer's cache ``leaves = (k_l, v_l, k_s,
    v_s)`` at index ``at`` (row and position); returns the written leaves.

    The values take the trailing shape of the leaf they land in: ``(h,
    hd)`` in the dense cache, the heads folded into one minor axis ``(h *
    hd,)`` in the paged pool (``serve.kv_cache.init_paged_cache``).

    ``k_s``/``v_s`` (``[..., h]`` a position, f32) exist under the int8
    layout only and are None otherwise.  There the new tokens quantize on
    write, per head and BEFORE the fold (values and their own
    per-position-per-head scales), and only stored history pays the 8-bit
    grid: a decode step still attends its EXACT current token, which the
    caller hands to attention beside the cache (storage is quantized, the
    in-flight value costs nothing to keep f32)."""
    k_l, v_l, k_s, v_s = leaves
    if k_s is not None:
        k, ks = _q_kv(k)
        v, vs = _q_kv(v)
        k_s, v_s = k_s.at[at].set(ks), v_s.at[at].set(vs)
    new = k.shape[:-2]  # one entry of ``at`` per new token
    return (
        k_l.at[at].set(k.reshape(new + k_l.shape[2:]).astype(k_l.dtype)),
        v_l.at[at].set(v.reshape(new + v_l.shape[2:]).astype(v_l.dtype)),
        k_s, v_s,
    )


def forward_decode(params, token, cache, pos, *, num_heads: int,
                   kernel: str = "gather", mesh=None):
    """Single-token decode step: next-token logits from the KV cache.

    ``token``: [B] int32 — each slot's current token; ``pos``: [B] int32 —
    the position that token occupies (per-slot: continuous batching runs
    slots at different depths); ``cache``: ``{"k", "v"}`` each
    ``[B, L, S, h, hd]`` (:mod:`serve.kv_cache` layout), plus
    ``{"k_scale", "v_scale"}`` ([B, L, S, h] f32) under the int8 layout —
    writes quantize (:func:`_write_kv`), reads dequantize fused into
    attention.

    Each layer scatters the new token's K/V into its cache *before*
    attention (each slot at its own position), then attends positions
    ``<= pos``: exactly :func:`forward`'s math restricted to one query row.

    ``kernel``: how attention consumes the cache (``ops.flash_decode``):
    ``"gather"`` is the legacy dense read (an int8 cache dequantized at
    history granularity); ``"flash"`` the paged flash-decode kernel (Pallas
    on TPU — in-tile dequant, f32 history never in HBM; the fused-XLA twin
    elsewhere, scales folded into the score/probability vectors, bitwise
    identical to gather for f32 caches).

    Returns ``(logits [B, vocab], new_cache)`` where ``new_cache`` has the
    token's K/V written at ``pos`` in every layer.  O(S·d) per token per
    layer — no S² term, THE reason the serve path exists.  Positions
    ``> pos`` are masked, so stale K/V from a previous occupant of the slot
    (or prefill padding) can never leak into attention.

    Jit with the cache donated (``serve.engine`` does) so the [B,L,S,h,hd]
    buffers update in place instead of doubling HBM per step.
    """
    x = _embed_at(params, token, pos)  # [B, d]
    quantized = quantized_cache(cache)

    def body(carry, xs):
        p, *leaves = xs

        def attend(q, k_t, v_t):
            k_l, v_l, k_s, v_s = written = _write_kv(
                leaves, (jnp.arange(pos.shape[0]), pos), k_t, v_t
            )
            ctx = _fd.decode_attention_dense(
                q, k_l, v_l, k_s, v_s, k_t, v_t, pos, kernel=kernel, mesh=mesh
            )
            return ctx, written

        return _block(p, carry, num_heads=num_heads, attend=attend)

    xs = (
        params["blocks"],
        jnp.moveaxis(cache["k"], 1, 0),
        jnp.moveaxis(cache["v"], 1, 0),
        jnp.moveaxis(cache["k_scale"], 1, 0) if quantized else None,
        jnp.moveaxis(cache["v_scale"], 1, 0) if quantized else None,
    )
    x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(body, x, xs)
    new_cache = {
        "k": jnp.moveaxis(k_new, 0, 1),
        "v": jnp.moveaxis(v_new, 0, 1),
    }
    if quantized:
        new_cache["k_scale"] = jnp.moveaxis(ks_new, 0, 1)
        new_cache["v_scale"] = jnp.moveaxis(vs_new, 0, 1)
    return _mm(x, params["head"]), new_cache


def _scan_pool(blocks, x, cache, attend_of, *, num_heads: int):
    """Walk the layers over a PAGED pool, in place: the one way the paged
    forwards (decode, chunk, verify) touch it.

    Every leaf ``[pages, L, page_size, h * hd]`` (scales ``[..., h]``) is
    viewed as rows ``[pages * L, page_size, h * hd]``, layer ``l`` of
    physical page ``p`` at row ``p * L + l``.  With the heads folded into
    the minor axis the device keeps the leaf row-major, so the view is a
    bitcast and no data moves (``serve.kv_cache.init_paged_cache`` says
    why).  The rows ride ``lax.scan`` as its
    CARRY, never as scanned input or stacked output and never transposed,
    so a layer's ``.at[rows, offs].set`` writes the donated pool where it
    lies and a call moves the positions it writes, not the pool.

    Each layer is :func:`_block` under ``attend_of(rows_of, leaves)``: the
    forward's callback over this layer's rows, ``leaves = (k, v, k_s,
    v_s)`` as :func:`_write_kv` takes and returns them (``k_s``/``v_s``
    None for an f32 pool), which the callback hands back written as its
    ``aux``; ``rows_of(pages)`` maps physical page ids to the layer's rows.
    Returns ``(x, new_cache)`` in the pool's own shape."""
    L = cache["k"].shape[1]
    names = ("k", "v", "k_scale", "v_scale")
    pool = tuple(
        cache[n].reshape((-1,) + cache[n].shape[2:]) if n in cache else None
        for n in names
    )

    def body(carry, xs):
        (x, pool), (p, l) = carry, xs
        attend = attend_of(lambda pages: pages * L + l, pool)
        return _block(p, x, num_heads=num_heads, attend=attend), None

    (x, pool), _ = jax.lax.scan(body, (x, pool), (blocks, jnp.arange(L)))
    return x, {
        n: rows.reshape(cache[n].shape)
        for n, rows in zip(names, pool) if rows is not None
    }


def forward_decode_paged(
    params, token, cache, pos, block_tables, *, num_heads: int,
    page_size: int, kernel: str = "gather", mesh=None,
):
    """Single-token decode step over the PAGED cache layout.

    Same contract as :func:`forward_decode` — ``token``/``pos``: [B] int32,
    returns ``(logits [B, vocab], new_cache)`` — but ``cache`` is the
    global page pool ``{"k", "v"}`` each ``[pages, L, page_size, h * hd]``
    (heads folded into the minor axis; the layer still splits its
    projections to heads, :func:`_write_kv` folds what it stores)
    and ``block_tables`` ([B, nb] int32) maps each slot's logical pages to
    physical ones: logical position ``j`` lives at ``(table[j //
    page_size], j % page_size)``.  Same write-then-attend order and the
    same two kernels (under ``"flash"`` pages stream directly).  Identical
    math to the dense path (the bit-exactness gate in
    ``tests/test_paged_cache.py`` pins it): the gathered page view
    reconstructs exactly the dense ``[B, S, h, hd]`` key/value sequence,
    padded with masked positions up to ``nb * page_size``.  Released slots
    point every table entry at the scratch page and sit at pos 0, so their
    writes land in the dustbin and never touch a live page.

    The pool is updated IN PLACE (:func:`_scan_pool`): a layer runs under
    ``block_tables * L + l`` and writes one position a slot, whatever the
    pool's size.  An int8 pool adds ``{"k_scale", "v_scale"}`` ([pages, L,
    page_size, h] f32) and matches the f32 paged path up to the 8-bit grid
    (``bench.py --quant`` reports agreement rate and MAE).
    """
    x = _embed_at(params, token, pos)  # [B, d]

    def attend_of(rows_of, pool):
        tables = rows_of(block_tables)

        def attend(q, k_t, v_t):
            page = tables[jnp.arange(pos.shape[0]), pos // page_size]
            k_l, v_l, k_s, v_s = written = _write_kv(
                pool, (page, pos % page_size), k_t, v_t
            )
            ctx = _fd.decode_attention_paged(
                q, k_l, v_l, k_s, v_s, k_t, v_t, pos, tables,
                page_size=page_size, kernel=kernel, mesh=mesh,
            )
            return ctx, written

        return attend

    x, new_cache = _scan_pool(
        params["blocks"], x, cache, attend_of, num_heads=num_heads
    )
    return _mm(x, params["head"]), new_cache


def forward_prefill_chunk(
    params, tokens, cache, block_table, offset, *, num_heads: int,
    page_size: int, kernel: str = "gather", mesh=None,
):
    """One CHUNK of a prompt prefilled against the paged cache.

    The chunked-prefill program: ``tokens`` [1, C] occupy logical
    positions ``[offset, offset + C)`` of ONE sequence whose physical
    pages are listed in ``block_table`` ([nb] int32).  Each layer writes
    the chunk's K/V into the pages first, then attends over the gathered
    page view — chunk token ``i`` sees every cached position
    ``<= offset + i``: the whole already-prefilled history (earlier
    chunks, shared prefix pages) plus the causal part of its own chunk.
    Exactly :func:`forward`'s math with the key space routed through the
    page pool.

    Returns ``(logits [1, C, vocab], new_cache)``, the pool updated in
    place (:func:`_scan_pool`).  Positions that overflow the block table
    (final-chunk padding) are routed to the scratch page, layer ``l``'s at
    its row ``l``; their outputs are garbage and the caller ignores them.

    Int8 pool: the chunk's K/V quantize on write (:func:`_write_kv`) and
    the page gather dequantizes into attention — so chunk token ``i``
    attends to the same cache-roundtripped history a later decode step
    will read, keeping prefill and decode numerics coherent.
    """
    b, C = tokens.shape
    if b != 1:
        raise ValueError(f"chunked prefill is per-sequence, got batch {b}")
    nb = block_table.shape[0]
    posns = offset + jnp.arange(C)  # [C] logical positions
    page_idx = posns // page_size
    in_range = page_idx < nb
    pages = jnp.where(
        in_range, block_table[jnp.minimum(page_idx, nb - 1)], 0
    )  # overflow (padding past max_seq) -> scratch page
    offs = posns % page_size
    x = _embed_at(params, tokens[0], posns)  # [C, d]

    def attend_of(rows_of, pool):
        rows = rows_of(pages)  # overflow -> the scratch page's own layer

        def attend(q, k_c, v_c):
            k_l, v_l, k_s, v_s = written = _write_kv(
                pool, (rows, offs), k_c, v_c
            )
            # Prefill attends over the cache-roundtripped values for the own
            # chunk TOO (no exact-self overlay on int8 pools, unlike decode):
            # per-token quantization is chunk-ALIGNMENT-invariant, so a
            # prefix-cache hit (which shifts the chunk offset by the shared
            # length) produces bit-identical logits to a cold run — an
            # exact-own-chunk window would make the numbers depend on where
            # the chunk boundaries fell.  Both kernels preserve this.
            ctx = _fd.chunk_attention(
                q, k_l, v_l, k_s, v_s, rows_of(block_table), posns,
                page_size=page_size, kernel=kernel, mesh=mesh,
            )
            return ctx, written

        return attend

    x, new_cache = _scan_pool(
        params["blocks"], x, cache, attend_of, num_heads=num_heads
    )
    return _mm(x, params["head"])[None], new_cache


def forward_verify(
    params, tokens, cache, pos, draft_len, *, num_heads: int,
    kernel: str = "gather", mesh=None,
):
    """Batched K+1-token verification step against the DENSE cache — the
    verifier half of speculative decoding (``spec/``).

    ``tokens``: [B, K1] int32 — column 0 is each slot's pending token,
    columns 1..K its drafted continuation; ``pos``: [B] int32 — the
    position column 0 occupies; ``draft_len``: [B] int32 in [0, K1-1] —
    how many of the K draft columns are real for each slot (slots near
    their budget or ``max_seq`` verify fewer; 0 degenerates to exactly a
    single-token decode step).

    Chunk-prefill-style write-then-attend (``forward_prefill_chunk``),
    batched over slots at per-slot positions: each layer first scatters
    the K/V of every VALID token (column ``j <= draft_len``) into the
    cache at ``pos + j``, then attends over the slot's full cache row
    with query ``j`` seeing positions ``<= pos + j`` — so the logits at
    column ``j`` are computed from exactly the history a sequential
    ``forward_decode`` walk would have seen, and the greedy argmax chain
    is bit-identical to non-speculative decode (``tests/test_spec.py``
    pins it position-for-position).  Invalid columns write NOWHERE
    (their scatter indices are pushed out of bounds and dropped) and
    their logits are garbage the caller must mask.

    Returns ``(logits [B, K1, vocab], new_cache)``.  The caller owns the
    rollback: positions past the accepted prefix hold rejected-draft K/V
    that must be scrubbed (``engine.scrub_slot`` / the spec decoder's
    batched rollback) before they could ever be exposed.

    f32 cache only: the int8 layout's exact-own-token overlay is
    per-query here, which cannot reproduce sequential decode's numerics
    bitwise — speculative decoding gates on the f32 cache.
    """
    if quantized_cache(cache):
        raise ValueError(
            "speculative verification supports the f32 cache layout only "
            "(the acceptance rule extends the decode==full-forward "
            "bit-exactness pin, which the int8 grid breaks)"
        )
    b, K1 = tokens.shape
    S = cache["k"].shape[2]
    posmat = pos[:, None] + jnp.arange(K1)[None]  # [B, K1]
    valid = jnp.arange(K1)[None] <= draft_len[:, None]
    x = _embed_at(params, tokens, posmat)  # [B, K1, d]
    # invalid columns scatter out of bounds -> dropped (never clamped:
    # a clamped write could collide with a valid column's position)
    wpos = jnp.where(valid, posmat, S)
    rows = jnp.arange(b)[:, None]

    def body(carry, xs):
        p, k_l, v_l = xs

        def attend(q, k_c, v_c):
            k_w = k_l.at[rows, wpos].set(k_c.astype(k_l.dtype), mode="drop")
            v_w = v_l.at[rows, wpos].set(v_c.astype(v_l.dtype), mode="drop")
            ctx = _fd.verify_attention_dense(
                q, k_w, v_w, posmat, kernel=kernel, mesh=mesh
            )
            return ctx, (k_w, v_w)

        return _block(p, carry, num_heads=num_heads, attend=attend)

    xs = (
        params["blocks"],
        jnp.moveaxis(cache["k"], 1, 0),
        jnp.moveaxis(cache["v"], 1, 0),
    )
    x, (k_new, v_new) = jax.lax.scan(body, x, xs)
    new_cache = {
        "k": jnp.moveaxis(k_new, 0, 1),
        "v": jnp.moveaxis(v_new, 0, 1),
    }
    return _mm(x, params["head"]), new_cache


def forward_verify_paged(
    params, tokens, cache, pos, draft_len, block_tables, *,
    num_heads: int, page_size: int, kernel: str = "gather", mesh=None,
):
    """Batched K+1-token verification step over the PAGED cache layout.

    Same contract as :func:`forward_verify` (``tokens`` [B, K1], per-slot
    ``pos``/``draft_len``, returns ``(logits [B, K1, vocab], new_cache)``)
    with the key space routed through the page pool: valid columns
    scatter to ``(table[(pos+j) // page_size], (pos+j) % page_size)``,
    invalid or out-of-table columns land in the scratch page (the
    dustbin — same convention as decode's released-slot lanes), and
    attention runs over the block-table-gathered page view masked to
    ``<= pos + j`` per query.  Bit-identical to the dense verify (the
    gathered view IS the dense key sequence) and therefore to sequential
    paged decode.  f32 pool only, like the dense verify.  The pool is
    updated in place, page ``p`` at row ``p * L + l`` (:func:`_scan_pool`).
    """
    if quantized_cache(cache):
        raise ValueError(
            "speculative verification supports the f32 cache layout only "
            "(the acceptance rule extends the decode==full-forward "
            "bit-exactness pin, which the int8 grid breaks)"
        )
    b, K1 = tokens.shape
    nb = block_tables.shape[1]
    posmat = pos[:, None] + jnp.arange(K1)[None]  # [B, K1]
    valid = jnp.arange(K1)[None] <= draft_len[:, None]
    x = _embed_at(params, tokens, posmat)  # [B, K1, d]
    rows = jnp.arange(b)[:, None]
    page_idx = posmat // page_size
    in_range = valid & (page_idx < nb)
    # invalid/overflow columns -> scratch page 0 (the dustbin), exactly
    # like forward_prefill_chunk's padding overflow
    pages = jnp.where(
        in_range, block_tables[rows, jnp.minimum(page_idx, nb - 1)], 0
    )
    offs = jnp.where(in_range, posmat % page_size, 0)

    def attend_of(rows_of, pool):
        wrows = rows_of(pages)

        def attend(q, k_c, v_c):
            k_l, v_l, _, _ = written = _write_kv(pool, (wrows, offs), k_c, v_c)
            ctx = _fd.verify_attention_paged(
                q, k_l, v_l, rows_of(block_tables), posmat,
                page_size=page_size, kernel=kernel, mesh=mesh,
            )
            return ctx, written

        return attend

    x, new_cache = _scan_pool(
        params["blocks"], x, cache, attend_of, num_heads=num_heads
    )
    return _mm(x, params["head"]), new_cache


# Which width dim of each stacked block leaf ZeRO-3 shards (leaf layout
# AFTER the stage dim is [L/S, ...]; ln scales stay replicated).
_ZERO3_WIDTH_DIM = {"qkv": 2, "proj": 1, "w_in": 2, "w_out": 1}


def forward_pipelined(
    params,
    tokens,
    *,
    num_heads: int,
    mesh,
    num_microbatches: int,
    remat: bool = False,
    attention: str = "dense",
    zero3_axis: Optional[str] = None,
) -> jax.Array:
    """Same function, stages sharded over the mesh's ``pipe`` axis.

    ``attention="flash"`` runs the causal Pallas kernel inside each stage —
    the kernel executes per-shard inside pipeline_apply's shard_map, so no
    extra mesh plumbing is needed.

    ``zero3_axis`` (e.g. ``"fsdp"``) composes the pipe axis with ZeRO-3
    weight sharding INSIDE each stage: every chip stores only a
    1/axis-size width-slice of its stage's qkv/proj/FF weights
    (``pipeline_apply``'s ``param_partition``) and all-gathers them per
    tick; the gather's transpose reduce-scatters the weight gradients
    back.  Without it a pipe×fsdp mesh keeps each stage's FULL weights
    resident per chip and GSPMD re-gathers at the shard_map boundary —
    correct, but no ZeRO-3 memory saving.  Exact same math either way
    (the gather reconstructs the full weights bit-for-bit).

    Pair with ``remat=True`` when the MEMORY saving is the point: without
    remat the backward saves each tick's gathered full-width weights as
    scan residuals, so peak HBM still holds full stage weights; the
    remat'd tick re-gathers in backward instead of saving.
    """
    from distributeddeeplearning_tpu.ops.pipeline import pipeline_apply

    n_stages = int(mesh.shape["pipe"])
    blocks = params["blocks"]
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers not divisible by {n_stages} pipe stages")
    staged = jax.tree_util.tree_map(
        lambda a: a.reshape(n_stages, L // n_stages, *a.shape[1:]), blocks
    )

    param_partition = None
    if zero3_axis is not None and int(mesh.shape[zero3_axis]) > 1:
        t = int(mesh.shape[zero3_axis])
        for name, dim in _ZERO3_WIDTH_DIM.items():
            # leaf layout [S, L/S, ...]: param_partition dim indexes skip
            # the stage dim, the staged leaf adds one more leading dim
            width = staged[name].shape[dim + 1]
            if width % t:
                raise ValueError(
                    f"{zero3_axis}={t} must divide {name}'s sharded width "
                    f"{width}"
                )
        param_partition = {
            name: tuple(
                zero3_axis if d == dim else None for d in range(3)
            )
            for name, dim in _ZERO3_WIDTH_DIM.items()
        }
        param_partition["ln1"] = None
        param_partition["ln2"] = None

        def stage_fn(stage_params, x):
            gathered = {
                k: jax.lax.all_gather(
                    v, zero3_axis, axis=_ZERO3_WIDTH_DIM[k], tiled=True
                )
                if k in _ZERO3_WIDTH_DIM
                else v
                for k, v in stage_params.items()
            }
            return _stack_scan(
                gathered, x, num_heads=num_heads, attention=attention
            )
    else:
        def stage_fn(stage_params, x):
            return _stack_scan(
                stage_params, x, num_heads=num_heads, attention=attention
            )

    x = _embed(params, tokens)
    x = pipeline_apply(
        stage_fn, staged, x, mesh=mesh, num_microbatches=num_microbatches,
        remat=remat, param_partition=param_partition,
    )
    return x @ params["head"]


def per_token_loss(
    params,
    tokens: jax.Array,
    *,
    num_heads: int,
    attention: str = "dense",
    attention_fn=None,
    remat: bool = False,
    loss_chunk: Optional[int] = None,
    unroll: int = 1,
) -> jax.Array:
    """Per-position next-token CE ``[b, s-1]`` WITHOUT the full logits.

    At long context the ``[b, s, vocab]`` f32 logits tensor is itself the
    memory wall (seq 64k × vocab 32k = 8.6 GB f32 — more than half a v5e's
    HBM before any activation).  This fuses the head matmul into the loss:
    a ``lax.scan`` over ``loss_chunk``-sized sequence chunks computes each
    chunk's logits, logsumexp and target gather, keeping peak logits
    memory O(chunk × vocab).  The chunk body is ``jax.checkpoint``-ed so
    backward RECOMPUTES chunk logits from the hidden states instead of
    saving them (without that, scan's saved residuals re-materialize the
    full logits and nothing is won).

    Exact same math as ``next_token_loss(forward(...), tokens)`` (f32 CE);
    ``loss_chunk=None`` falls back to the one-shot head matmul.
    """
    b, s = tokens.shape
    if s < 2:
        raise ValueError(
            f"next-token loss needs sequence length >= 2, got {s}"
        )
    x = _embed(params, tokens)
    x = _stack_scan(
        params["blocks"], x, num_heads=num_heads, attention=attention,
        attention_fn=attention_fn, remat=remat, unroll=unroll,
    )
    h = x[:, :-1]  # [b, s-1, d] — position t predicts token t+1
    labels = tokens[:, 1:]
    n = s - 1
    head = params["head"]

    def chunk_ce(hc, lc):
        logits = (hc @ head).astype(jnp.float32)  # [b, c, V]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return lse - tgt

    if loss_chunk is None or loss_chunk >= n:
        return chunk_ce(h, labels)
    if n % loss_chunk:
        raise ValueError(
            f"loss_chunk {loss_chunk} must divide seq_len-1 = {n}"
        )
    nch = n // loss_chunk
    d = h.shape[-1]
    h_c = h.reshape(b, nch, loss_chunk, d).swapaxes(0, 1)
    lab_c = labels.reshape(b, nch, loss_chunk).swapaxes(0, 1)

    def body(carry, xs):
        hc, lc = xs
        return carry, chunk_ce(hc, lc)

    _, losses = jax.lax.scan(jax.checkpoint(body), None, (h_c, lab_c))
    return losses.swapaxes(0, 1).reshape(b, n)


def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Causal LM loss: predict token t+1 from positions ≤ t.

    Delegates to the framework's one cross-entropy implementation
    (``train.step.cross_entropy_loss``) after the causal shift.
    """
    from distributeddeeplearning_tpu.train.step import cross_entropy_loss

    b, s = tokens.shape
    if s < 2:
        raise ValueError(
            f"next-token loss needs sequence length >= 2, got {s}"
        )
    # Keep the shifted logits 3-D: cross_entropy_loss reduces over the last
    # dim and means over the rest, and flattening to [b·(s-1), V] forced XLA
    # to COMPACT the non-contiguous slice — a 1 GB copy (6.4 ms) per step on
    # the 12-layer seq-2048 LM that the strided view avoids entirely.
    return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
