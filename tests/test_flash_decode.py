"""Paged flash-decode kernel (``ops.flash_decode``) — PR 12 pins.

The load-bearing guarantees:

- **f32 bit-exactness**: ``kernel="flash"`` decode/chunk/verify logits
  are BITWISE identical to the gather-dense reference at every position
  on both layouts (off-TPU the flash twin is op-for-op the gather
  program — the decode==full-forward pin extends through it for free),
  pinned over a teacher-forced multi-position walk;
- **Pallas kernel math**: the actual kernel (interpret mode on CPU,
  ``kernel="pallas"``) matches the gather reference to f32 tolerance
  with identical argmaxes, on both layouts, f32 AND int8 — including the
  in-tile dequant and the exact-own-token overlay;
- **int8 scale-exactness**: the flash int8 path reads the SAME int8
  codes + scales the gather path reads (cache writes are kernel-
  independent, pinned bitwise) and its folded dequant tracks the
  history-granular reference to float tolerance with identical greedy
  choices; the flash int8 engine is run-to-run deterministic;
- **prefix-cache interplay**: an int8 flash engine decodes bit-
  identically on a prefix-cache hit whose shared length is NOT a chunk
  multiple (chunk-alignment invariance survives the kernel);
- **spec interplay**: rollback-then-redecode over the flash kernel —
  a forced-rejection speculative step followed by rollback leaves the
  cache decoding exactly as a never-drafted run (both layouts ride the
  same kernel through ``forward_verify*``);
- ``bench.py --quant`` (which now gates the kv_int8 both-axes win and
  the f32 flash==gather token identity) smokes end-to-end on CPU.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward_decode,
    forward_decode_paged,
    forward_prefill_chunk,
    init_params,
)
from distributeddeeplearning_tpu.ops import flash_decode as fd
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    PagedInferenceEngine,
    init_cache,
    init_paged_cache,
    synthetic_requests,
)

CFG = dict(num_layers=2, d_model=32, num_heads=2, d_ff=48, vocab_size=53,
           max_len=64)
HEADS = CFG["num_heads"]
HD = CFG["d_model"] // HEADS
L = CFG["num_layers"]
S = 64
PS = 8  # page size >= fd.PALLAS_BLOCK_FLOOR so "pallas" runs the kernel
B = 2


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), **CFG)


def _paged_setup(dtype=None):
    nb = S // PS
    cache = init_paged_cache(
        num_pages=B * nb + 2, num_layers=L, page_size=PS, num_heads=HEADS,
        head_dim=HD, dtype=dtype or jnp.float32,
    )
    # slot i owns pages [1 + i*nb, 1 + (i+1)*nb) — fixed disjoint tables
    tables = jnp.asarray(
        1 + np.arange(B)[:, None] * nb + np.arange(nb)[None], jnp.int32
    )
    return cache, tables


_WALKS: dict = {}


def _decode_walk(params, kernel, *, layout, dtype=None, steps=16):
    """Teacher-forced decode walk from an empty cache: fixed token
    stream, per-step logits collected — positions 0..steps-1 so every
    comparison covers a different history depth.  Memoized per
    (kernel, layout, dtype): several tests compare against the same
    gather reference, and the walk is the expensive part."""
    key = (kernel, layout, str(dtype), steps)
    if key in _WALKS:
        return _WALKS[key]
    rng = np.random.default_rng(5)
    toks = rng.integers(0, CFG["vocab_size"], size=(steps, B)).astype(
        np.int32
    )
    if layout == "paged":
        cache, tables = _paged_setup(dtype)
    else:
        cache = init_cache(
            batch_slots=B, num_layers=L, max_seq=S, num_heads=HEADS,
            head_dim=HD, dtype=dtype or jnp.float32,
        )
    out = []
    for i in range(steps):
        pos = jnp.full((B,), i, jnp.int32)
        if layout == "paged":
            logits, cache = forward_decode_paged(
                params, jnp.asarray(toks[i]), cache, pos, tables,
                num_heads=HEADS, page_size=PS, kernel=kernel,
            )
        else:
            logits, cache = forward_decode(
                params, jnp.asarray(toks[i]), cache, pos,
                num_heads=HEADS, kernel=kernel,
            )
        out.append(np.asarray(logits))
    _WALKS[key] = (np.stack(out), cache)
    return _WALKS[key]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_flash_f32_bit_exact_vs_gather_every_position(params, layout):
    """THE f32 pin: flash logits == gather logits BITWISE at every
    position of a 20-step walk, and the caches land bit-identical."""
    ref, c_ref = _decode_walk(params, "gather", layout=layout)
    got, c_got = _decode_walk(params, "flash", layout=layout)
    np.testing.assert_array_equal(ref, got)
    for key in c_ref:
        np.testing.assert_array_equal(
            np.asarray(c_ref[key]), np.asarray(c_got[key])
        )


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("dtype", [None, jnp.int8])
def test_pallas_kernel_matches_gather_reference(params, layout, dtype):
    """The actual Pallas kernel (interpret mode on CPU): online-softmax
    split-K over pages — f32-tolerance match against the gather-dense
    reference with identical argmaxes at every walk position, f32 and
    int8 (in-tile dequant + exact-own-token overlay) on both layouts."""
    ref, _ = _decode_walk(params, "gather", layout=layout, dtype=dtype)
    got, _ = _decode_walk(params, "pallas", layout=layout, dtype=dtype)
    np.testing.assert_allclose(got, ref, atol=5e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        ref.argmax(axis=-1), got.argmax(axis=-1)
    )


@pytest.mark.parametrize("heads", [32, 12])
def test_pallas_decode_reads_nothing_past_each_slot(heads):
    """The one-row f32 kernel at the served widths (heads of 64, pages of
    64): four slots at positions 0, 63, 64 and 300, the pages each reserves
    past its position filled with NaN, and a lane whose row is all scratch
    at a stale position.  Against the gather reference over the same pool
    with those pages finite (it gathers them, and ``0 × NaN`` would carry
    into its sums): every slot to f32 tolerance, no NaN in any lane."""
    hd, page, nb = 64, 64, 8
    pos = np.asarray([0, 63, 64, 300, 200], np.int32)
    tables = np.zeros((len(pos), nb), np.int32)  # page 0: the scratch page
    past = []
    for lane, p in enumerate(pos[:4]):
        tables[lane] = 1 + lane * nb + np.arange(nb)
        past += list(tables[lane, p // page + 1:])
    rng = np.random.default_rng(11)
    k, v = (
        rng.normal(size=(1 + 4 * nb, page, heads * hd)).astype(np.float32)
        for _ in range(2)
    )
    q = jnp.asarray(rng.normal(size=(len(pos), heads, hd)).astype(np.float32))
    ref = fd._gather_decode_paged(
        q, jnp.asarray(k), jnp.asarray(v), None, None, q, q,
        jnp.asarray(pos), jnp.asarray(tables), page_size=page,
    )
    k[past], v[past] = np.nan, np.nan
    got = np.asarray(fd.decode_attention_paged(
        q, jnp.asarray(k), jnp.asarray(v), None, None, q, q,
        jnp.asarray(pos), jnp.asarray(tables), page_size=page,
        kernel="pallas",
    ))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:4], np.asarray(ref)[:4], atol=5e-5,
                               rtol=1e-5)


def test_page_map_repeats_the_last_page_past_the_newest_position():
    """A grid step past a slot's newest position names that slot's last
    page, so the pipeline fetches nothing new for it."""
    page = fd._page_map(64)
    tables = jnp.arange(20, dtype=jnp.int32).reshape(2, 10)
    newest = jnp.asarray([130, 639], jnp.int32)
    assert [int(page(0, j, tables, newest)[0]) for j in range(10)] == [
        0, 1, 2, 2, 2, 2, 2, 2, 2, 2
    ]
    assert [int(page(1, j, tables, newest)[0]) for j in range(10)] == list(
        range(10, 20)
    )


def test_decode_kernel_name_is_what_the_roofline_reads():
    """``flash_decode_roofline`` sums the trace events named
    ``flash_decode_decode_*``: the one-row f32 form keeps its name."""
    assert fd._kernel_name(1, False, False) == "flash_decode_decode_f32"


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_int8_flash_scale_exact_vs_gather(params, layout):
    """Int8 scale-exactness: fed the SAME quantized cache state, the
    flash read (scales folded into the score/probability vectors)
    matches the history-granular gather dequant to fold-reassociation
    tolerance with identical greedy argmaxes — pinned at the ops level
    (one attention call, no cross-layer feedback) AND over a full walk
    (where attention deltas feed the residual stream, so tolerance is
    the honest contract — int8 fidelity itself is the 99% gate in
    bench --quant).  Run-to-run determinism is pinned exactly."""
    # ops level: identical cache leaves in, fold order the ONLY delta
    rng = np.random.default_rng(9)
    nb = S // PS
    P = B * nb + 2
    pool = lambda *sh: jnp.asarray(  # noqa: E731
        rng.integers(-127, 128, size=sh, dtype=np.int8)
    )
    scales = lambda *sh: jnp.asarray(  # noqa: E731
        rng.uniform(0.01, 0.1, size=sh).astype(np.float32)
    )
    f32 = lambda *sh: jnp.asarray(  # noqa: E731
        rng.normal(size=sh).astype(np.float32)
    )
    q3, k_t, v_t = f32(B, HEADS, HD), f32(B, HEADS, HD), f32(B, HEADS, HD)
    pos = jnp.asarray([S - 2, S // 2], jnp.int32)
    if layout == "paged":
        _, tables = _paged_setup()
        args = (
            q3, pool(P, PS, HEADS * HD), pool(P, PS, HEADS * HD),  # folded
            scales(P, PS, HEADS), scales(P, PS, HEADS), k_t, v_t, pos,
            tables,
        )
        ref1 = fd.decode_attention_paged(*args, page_size=PS,
                                         kernel="gather")
        got1 = fd.decode_attention_paged(*args, page_size=PS,
                                         kernel="flash")
    else:
        args = (
            q3, pool(B, S, HEADS, HD), pool(B, S, HEADS, HD),
            scales(B, S, HEADS), scales(B, S, HEADS), k_t, v_t, pos,
        )
        ref1 = fd.decode_attention_dense(*args, kernel="gather")
        got1 = fd.decode_attention_dense(*args, kernel="flash")
    np.testing.assert_allclose(
        np.asarray(got1), np.asarray(ref1), atol=2e-6, rtol=1e-5
    )

    # walk level: greedy choices identical, logits within tolerance
    ref, _ = _decode_walk(params, "gather", layout=layout, dtype=jnp.int8)
    got, _ = _decode_walk(params, "flash", layout=layout, dtype=jnp.int8)
    np.testing.assert_allclose(got, ref, atol=5e-5, rtol=1e-5)
    np.testing.assert_array_equal(ref.argmax(axis=-1), got.argmax(axis=-1))
    # determinism: a fresh (shorter, so the memo can't answer) walk
    # reproduces the same prefix bit-for-bit
    again, _ = _decode_walk(
        params, "flash", layout=layout, dtype=jnp.int8, steps=12
    )
    np.testing.assert_array_equal(got[:12], again)


def test_chunk_attention_flash_bit_exact_f32(params):
    """Chunked prefill through the kernel dispatch: f32 flash == gather
    bitwise, chunk by chunk, including the non-chunk-aligned offsets a
    prefix hit produces."""
    prompt = np.arange(1, 25, dtype=np.int32)  # 24 tokens, 3 pages
    for offset in (0, 12):  # 12 = mid-chunk, the prefix-hit shape
        caches = {}
        for kernel in ("gather", "flash"):
            cache, tables = _paged_setup()
            lg, cache = forward_prefill_chunk(
                params, jnp.asarray(prompt[offset:][None]), cache,
                tables[0], jnp.int32(offset), num_heads=HEADS,
                page_size=PS, kernel=kernel,
            )
            caches[kernel] = (np.asarray(lg), cache)
        np.testing.assert_array_equal(
            caches["gather"][0], caches["flash"][0]
        )
        for key in caches["gather"][1]:
            np.testing.assert_array_equal(
                np.asarray(caches["gather"][1][key]),
                np.asarray(caches["flash"][1][key]),
            )


def test_int8_flash_prefix_hit_non_chunk_multiple(params):
    """Engine-level int8 + flash kernel: a prefix-cache hit whose shared
    length (12) is NOT a multiple of prefill_chunk (16) decodes bit-
    identically to a cold run — quantized prefill stays chunk-alignment-
    invariant through the kernel."""
    reqs = synthetic_requests(
        6, vocab_size=CFG["vocab_size"], max_prompt=12, min_prompt=4,
        shared_prefix_len=12, rng=np.random.default_rng(3),
    )
    kw = dict(num_heads=HEADS, batch_slots=2, max_seq=48, page_size=4,
              prefill_chunk=16, rng=jax.random.key(1),
              cache_dtype=jnp.int8, decode_kernel="flash")
    hit = PagedInferenceEngine(params, **kw)
    res_h, rep_h = ContinuousBatchingScheduler(
        hit, max_new_tokens=6
    ).run(list(reqs))
    miss = PagedInferenceEngine(params, prefix_cache=False, **kw)
    res_m, rep_m = ContinuousBatchingScheduler(
        miss, max_new_tokens=6
    ).run(list(reqs))
    assert rep_h.prefix_hit_rate > 0.0 and rep_m.prefix_hit_rate == 0.0
    assert rep_h.decode_kernel == "flash"
    assert {r.uid: r.tokens for r in res_h} == {
        r.uid: r.tokens for r in res_m
    }
    hit.allocator.check()


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_rollback_then_redecode_matches_never_drafted(params, layout):
    """Spec interplay on the flash kernel: draft K tokens through a
    garbage drafter (guaranteed total rejection), verify, roll the
    rejected tail back, then KEEP DECODING — the continued stream must
    be bit-identical to a run that never drafted (rollback restored the
    cache exactly, through the same kernel decode reads)."""
    from distributeddeeplearning_tpu.spec import SpeculativeDecoder
    from distributeddeeplearning_tpu.spec.drafter import Drafter

    class GarbageDrafter(Drafter):
        name = "garbage"

        def bind(self, engine):
            self._vocab = engine.vocab_size

        def propose(self, cache, tokens, pos):
            # propose an impossible constant stream; leaves the cache
            # untouched (the verify writes are what rollback must undo)
            return jnp.full_like(tokens, self._vocab - 1), cache

    def build():
        kw = dict(num_heads=HEADS, batch_slots=B, max_seq=S,
                  rng=jax.random.key(1), decode_kernel="flash")
        if layout == "paged":
            return PagedInferenceEngine(params, page_size=PS, **kw)
        from distributeddeeplearning_tpu.serve import InferenceEngine

        return InferenceEngine(
            params, prefill_attention="dense", **kw
        )

    prompt = [3, 1, 4, 1, 5, 9, 2, 6]

    # reference: plain decode walk, never drafted
    eng_ref = build()
    if layout == "paged":
        first_ref = eng_ref.prefill(0, prompt, max_new_tokens=10)
    else:
        first_ref = eng_ref.prefill(0, prompt)
    toks = np.zeros(B, np.int32)
    pos = np.zeros(B, np.int32)
    stream_ref = [first_ref]
    cur = first_ref
    for i in range(6):
        toks[0] = cur
        pos[0] = len(prompt) + i
        cur = int(eng_ref.decode(toks, pos)[0])
        stream_ref.append(cur)

    # candidate: one forced-rejection spec step + rollback, then decode
    eng = build()
    spec = SpeculativeDecoder(eng, drafter=GarbageDrafter(),
                              draft_tokens=3)
    if layout == "paged":
        first = eng.prefill(0, prompt, max_new_tokens=10)
    else:
        first = eng.prefill(0, prompt)
    assert first == first_ref
    toks = np.zeros(B, np.int32)
    toks[0] = first
    pos = np.zeros(B, np.int32)
    pos[0] = len(prompt)
    dlen = np.zeros(B, np.int32)
    dlen[0] = 3
    res = spec.step(toks, pos, dlen)
    assert int(res.accepted[0]) == 0  # garbage drafts: total rejection
    # commit only the bonus token, roll the rejected tail back
    spec.rollback(pos, np.ones(B, np.int32))
    committed = int(res.tokens[0, 0])
    assert committed == stream_ref[1]
    # redecode the rest plainly — bit-identical to never-drafted
    cur = committed
    stream = [first, committed]
    for i in range(1, 6):
        toks[0] = cur
        pos[0] = len(prompt) + i
        cur = int(eng.decode(toks, pos)[0])
        stream.append(cur)
    assert stream == stream_ref[:7]


def test_resolve_kernel_contract():
    assert fd.resolve_kernel("auto") == "flash"
    assert fd.resolve_kernel("flash") == "flash"
    assert fd.resolve_kernel("gather") == "gather"
    with pytest.raises(ValueError, match="unknown decode kernel"):
        fd.resolve_kernel("fused")
    # engines resolve at construction and report provenance
    eng = PagedInferenceEngine(
        init_params(jax.random.key(0), **CFG), num_heads=HEADS,
        batch_slots=1, max_seq=16, page_size=8,
    )
    assert eng.decode_kernel == "flash"


@pytest.mark.timeout(280)
def test_bench_quant_smoke_flash_kernel(tmp_path):
    """CPU smoke of the PR-12 bench: 5 configs (flash + gather exhibits),
    the f32 flash==gather token identity asserted in-run, artifact
    carries kernel provenance.  --steps-cap keeps it in the fast tier;
    the full-geometry run (which also gates the kv_int8 speed win) is
    the committed-artifact path."""
    import json

    report = tmp_path / "quant_smoke.json"
    out = subprocess.run(
        [
            sys.executable, "bench.py", "--quant", "--small",
            "--serve-requests", "4", "--batch-slots", "2",
            "--max-new-tokens", "6", "--steps-cap", "40",
            "--report", str(report),
        ],
        capture_output=True, text=True, timeout=260,
        cwd=str(Path(__file__).resolve().parents[1]),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    line = json.loads(report.read_text())
    assert line["flash_f32_bit_identical_to_gather"] is True
    assert line["decode_kernel"]["kv_int8"] == "flash"
    assert line["decode_kernel"]["kv_int8_gather"] == "gather"
    assert set(line["decode_tokens_per_sec"]) == {
        "f32", "kv_int8", "kv_w_int8", "f32_gather", "kv_int8_gather"
    }
