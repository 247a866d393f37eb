"""Paged KV cache: allocator invariants, prefix reuse, chunked prefill,
and the dense-vs-paged bit-exactness gate.

The load-bearing guarantee mirrors the dense suite's: decode through the
page pool + block tables must produce the SAME tokens as the dense layout
(and both must match the full-forward oracle) — the paged layout is a
memory-management change, never a math change.  On top of that the
allocator's alloc/free/refcount/prefix-eviction invariants are exercised
directly (``PageAllocator.check``), and admission backpressure is pinned:
an out-of-pages pool queues requests instead of crashing, and a request
that can never fit fails loudly instead of deadlocking.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward,
    forward_decode_paged,
    forward_prefill,
    forward_prefill_chunk,
    init_params,
)
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    OutOfPages,
    PageAllocator,
    PagedInferenceEngine,
    Request,
    cache_bytes,
    init_paged_cache,
    insert_pages,
    page_bytes,
    pages_for,
    synthetic_requests,
)

CFG = dict(num_layers=3, d_model=32, num_heads=4, d_ff=64, vocab_size=61,
           max_len=64)
HEADS = CFG["num_heads"]
HEAD_DIM = CFG["d_model"] // HEADS


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), **CFG)


def _naive_greedy(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([toks], jnp.int32),
                         num_heads=HEADS)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# --------------------------------------------------------------------------
# allocator
# --------------------------------------------------------------------------

def test_allocator_alloc_free_refcount_invariants():
    a = PageAllocator(6)
    assert a.available == 6 and a.pages_in_use == 0
    pages = a.alloc(4)
    a.check()
    assert len(set(pages)) == 4 and all(1 <= p <= 6 for p in pages)
    assert a.pages_in_use == 4
    assert all(a.refcount(p) == 1 for p in pages)
    a.incref(pages[0])
    a.decref(pages[0])
    assert a.refcount(pages[0]) == 1  # still live after the paired drop
    for p in pages:
        a.decref(p)
    a.check()
    assert a.available == 6  # everything returned
    with pytest.raises(ValueError, match="non-live"):
        a.decref(pages[0])
    with pytest.raises(OutOfPages):
        a.alloc(7)
    a.check()  # a failed alloc must not leak partial allocations
    assert a.available == 6


def test_allocator_prefix_reclaim_and_lru_eviction():
    a = PageAllocator(3)
    pages = a.alloc(3)
    a.register_prefix(("k0",), pages[0])
    a.register_prefix(("k1",), pages[1])
    for p in pages:
        a.decref(p)
    a.check()
    # registered pages are reclaimable (still findable), not freed
    assert a.available == 3
    assert a.lookup_prefix(("k0",)) == pages[0]
    # resurrect k1, then force eviction: k0 is the LRU victim
    a.incref(a.lookup_prefix(("k1",)))
    fresh = a.alloc(2)  # 1 free + must evict k0
    a.check()
    assert a.lookup_prefix(("k0",)) is None, "evicted entry still resolvable"
    assert a.lookup_prefix(("k1",)) == pages[1]
    assert pages[0] in fresh
    with pytest.raises(ValueError, match="non-live"):
        a.incref(pages[0] if pages[0] not in fresh else 99)


def test_allocator_clear_prefix_returns_pages():
    a = PageAllocator(4)
    pages = a.alloc(2)
    a.register_prefix(("x",), pages[0])
    a.decref(pages[0])
    a.decref(pages[1])
    a.clear_prefix()
    a.check()
    assert a.available == 4
    assert a.lookup_prefix(("x",)) is None
    assert a.prefix_entries == 0


# --------------------------------------------------------------------------
# model-level: paged decode / chunked prefill vs the dense oracle
# --------------------------------------------------------------------------

def test_paged_decode_matches_full_forward_every_position(params):
    """Identity block tables: paged decode from an empty pool == full
    forward at every position (the dense suite's acceptance pin, routed
    through pages)."""
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, CFG["vocab_size"], (2, 12)),
        jnp.int32,
    )
    b, s = tokens.shape
    page_size = 4
    full = np.asarray(forward(params, tokens, num_heads=HEADS))
    nb = pages_for(16, page_size)
    cache = init_paged_cache(
        num_pages=b * nb, num_layers=CFG["num_layers"], page_size=page_size,
        num_heads=HEADS, head_dim=HEAD_DIM,
    )
    # slot i owns pages [1 + i*nb, 1 + (i+1)*nb)
    tables = jnp.asarray(
        [[1 + i * nb + j for j in range(nb)] for i in range(b)], jnp.int32
    )
    for t in range(s):
        logits, cache = forward_decode_paged(
            params, tokens[:, t], cache, jnp.full((b,), t, jnp.int32),
            tables, num_heads=HEADS, page_size=page_size,
        )
        np.testing.assert_allclose(
            np.asarray(logits), full[:, t], atol=1e-5,
            err_msg=f"paged decode diverged at position {t}",
        )


def test_chunked_prefill_matches_forward(params):
    """Prefill in 4-token chunks == the monolithic forward's logits at
    every chunk's real positions, and the written pages equal
    forward_prefill's K/V."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, CFG["vocab_size"], 11).tolist()
    page_size, chunk = 4, 4
    full = np.asarray(
        forward(params, jnp.asarray([prompt], jnp.int32), num_heads=HEADS)
    )
    _, k_ref, v_ref = forward_prefill(
        params, jnp.asarray([prompt], jnp.int32), num_heads=HEADS
    )
    nb = pages_for(16, page_size)
    cache = init_paged_cache(
        num_pages=nb, num_layers=CFG["num_layers"], page_size=page_size,
        num_heads=HEADS, head_dim=HEAD_DIM,
    )
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)
    off = 0
    while off < len(prompt):
        real = min(chunk, len(prompt) - off)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :real] = prompt[off:off + real]
        logits, cache = forward_prefill_chunk(
            params, jnp.asarray(toks), cache, table, jnp.int32(off),
            num_heads=HEADS, page_size=page_size,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0, :real], full[0, off:off + real],
            atol=1e-5, err_msg=f"chunk at offset {off} diverged",
        )
        off += real
    # page contents == the monolithic prefill's K/V, page by page
    k_pages = np.asarray(cache["k"])  # [pages, L, ps, h * hd]
    k_want = np.asarray(k_ref).reshape(k_ref.shape[:3] + (-1,))  # heads folded
    for j in range(len(prompt)):
        np.testing.assert_allclose(
            k_pages[1 + j // page_size, :, j % page_size],
            k_want[0, :, j], atol=1e-6,
        )


def test_insert_pages_roundtrip(params):
    """insert_pages scatters [L, P, h, hd] K/V into listed pages, the heads
    folded into the pool's minor axis (head i at lanes [i*hd, (i+1)*hd))."""
    tokens = jnp.asarray([[5, 17, 3, 42, 8, 9, 11, 2]], jnp.int32)
    _, k, v = forward_prefill(params, tokens, num_heads=HEADS)
    cache = init_paged_cache(
        num_pages=4, num_layers=CFG["num_layers"], page_size=4,
        num_heads=HEADS, head_dim=HEAD_DIM,
    )
    cache = insert_pages(
        cache, k[0], v[0], jnp.asarray([2, 3], jnp.int32), page_size=4
    )
    assert cache["k"].shape == (5, CFG["num_layers"], 4, HEADS * HEAD_DIM)
    k_want = np.asarray(k).reshape(k.shape[:3] + (-1,))
    np.testing.assert_allclose(
        np.asarray(cache["k"])[2], k_want[0, :, 0:4], atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(cache["k"])[3, :, 2], k_want[0, :, 6], atol=1e-6,
    )
    np.testing.assert_allclose(  # head 1 of a position is its second lane group
        np.asarray(cache["v"])[3, :, 2, HEAD_DIM:2 * HEAD_DIM],
        np.asarray(v)[0, :, 6, 1], atol=1e-6,
    )
    assert page_bytes(cache) == cache_bytes(cache) // 5  # 4 pages + scratch


# --------------------------------------------------------------------------
# engine + scheduler: bit-exactness, prefix reuse, backpressure
# --------------------------------------------------------------------------

def test_paged_engine_greedy_matches_dense_and_oracle(params):
    """THE acceptance gate: identical (seed, request order) greedy runs
    produce bit-identical token sequences under both layouts, across
    mixed prompt lengths that exercise chunking and slot reuse."""
    rng = np.random.default_rng(2)
    prompts = {
        f"r{i}": rng.integers(1, CFG["vocab_size"],
                              rng.integers(2, 21)).tolist()
        for i in range(8)
    }
    reqs = lambda: [Request(uid=u, prompt=p) for u, p in prompts.items()]  # noqa: E731

    dense = InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                            max_seq=32, prefill_attention="dense")
    d_res, _ = ContinuousBatchingScheduler(
        dense, max_new_tokens=4).run(reqs())
    paged = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                                 max_seq=32, page_size=4, prefill_chunk=8)
    p_res, p_rep = ContinuousBatchingScheduler(
        paged, max_new_tokens=4).run(reqs())

    d_map = {r.uid: r.tokens for r in d_res}
    p_map = {r.uid: r.tokens for r in p_res}
    assert d_map == p_map, "paged diverged from dense"
    for uid, toks in p_map.items():
        assert toks == _naive_greedy(params, prompts[uid], 4), uid
    assert p_rep.kv_layout == "paged"
    assert p_rep.kv_bytes_peak < p_rep.kv_bytes  # never filled the pool
    # every page returned on completion
    paged.allocator.check()
    assert paged.allocator.pages_in_use == 0


def test_prefix_reuse_hit_and_miss(params):
    """Shared system-prompt workload: later requests map the shared full
    pages (nonzero hit rate), outputs still match the oracle; a
    no-prefix engine records zero hits on the same traffic."""
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, CFG["vocab_size"], 12).tolist()
    prompts = {
        f"s{i}": prefix + rng.integers(1, CFG["vocab_size"], 4).tolist()
        for i in range(5)
    }
    reqs = lambda: [Request(uid=u, prompt=p) for u, p in prompts.items()]  # noqa: E731

    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                               max_seq=32, page_size=4, prefill_chunk=8)
    res, rep = ContinuousBatchingScheduler(eng, max_new_tokens=3).run(reqs())
    assert rep.prefix_hit_rate > 0
    assert eng.prefix_hit_tokens >= 12 * 2  # later requests reuse >= 3 pages
    for r in res:
        assert r.tokens == _naive_greedy(params, prompts[r.uid], 3), r.uid
    eng.allocator.check()

    miss = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                                max_seq=32, page_size=4, prefill_chunk=8,
                                prefix_cache=False)
    _, mrep = ContinuousBatchingScheduler(miss, max_new_tokens=3).run(reqs())
    assert mrep.prefix_hit_rate == 0.0


def test_prefix_cache_never_shares_decode_written_pages(params):
    """A page only partially covered by the prompt takes decode writes and
    must never be shared: a second request whose prompt extends the first
    one's beyond the last FULL page gets fresh pages for the tail, and
    its outputs stay oracle-exact."""
    base = [7, 3, 11, 9, 2, 5]  # 6 tokens, page_size 4 -> one full page
    # ONE slot: request b admits only after a completes, so a's pages are
    # registered and the share is observable
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=1,
                               max_seq=32, page_size=4, prefill_chunk=8)
    sched = ContinuousBatchingScheduler(eng, max_new_tokens=4)
    res, rep = sched.run([
        Request(uid="a", prompt=base),
        Request(uid="b", prompt=base),  # same prompt: shares page 0 only
    ])
    for r in res:
        assert r.tokens == _naive_greedy(params, base, 4), r.uid
    # only the single FULL page (4 of 6 prompt tokens) is shareable
    assert eng.prefix_hit_tokens == 4


def test_out_of_pages_backpressure_and_oversized_request(params):
    """A pool smaller than the offered load queues requests (every one
    still completes, oracle-exact); a request larger than the POOL fails
    as an error instead of deadlocking the queue."""
    rng = np.random.default_rng(4)
    prompts = {
        f"r{i}": rng.integers(1, CFG["vocab_size"], 8).tolist()
        for i in range(5)
    }
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=4,
                               max_seq=32, page_size=4, num_pages=6,
                               prefill_chunk=8)
    res, rep = ContinuousBatchingScheduler(eng, max_new_tokens=4).run(
        [Request(uid=u, prompt=p) for u, p in prompts.items()]
    )
    assert rep.finish_reasons == {"length": 5}
    for r in res:
        assert r.tokens == _naive_greedy(params, prompts[r.uid], 4), r.uid
    # backpressure showed up as queue wait, and occupancy never exceeded
    # what 6 pages admit (3 tokens/page x 6 = 24 < 4 slots x 12 needed)
    assert rep.queue_wait_s["max"] > 0
    eng.allocator.check()
    assert eng.allocator.available == 6

    big = Request(uid="big", prompt=list(range(1, 28)))  # 27 + 4 > 24
    res2, rep2 = ContinuousBatchingScheduler(eng, max_new_tokens=4).run([big])
    assert res2[0].finish_reason == "error"
    assert "pool holds" in res2[0].error
    eng.allocator.check()


def test_engine_prefill_begin_validation_and_release(params):
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                               max_seq=16, page_size=4, prefill_chunk=8)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.prefill_begin(0, [], 4)
    with pytest.raises(ValueError, match="no room"):
        eng.prefill_begin(0, list(range(1, 17)), 4)
    with pytest.raises(ValueError, match="slot"):
        eng.prefill_begin(5, [1, 2], 4)
    task = eng.prefill_begin(0, [1, 2, 3], 4)
    with pytest.raises(ValueError, match="still holds pages"):
        eng.prefill_begin(0, [4, 5], 4)
    assert eng.allocator.pages_in_use == pages_for(3 + 4, 4)
    eng.release(0)
    assert eng.allocator.pages_in_use == 0
    assert (eng.block_tables[0] == 0).all()
    # direct OutOfPages from prefill_begin when the pool is exhausted
    tiny = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                                max_seq=16, page_size=4, num_pages=2,
                                prefill_chunk=8)
    tiny.prefill_begin(0, [1, 2, 3, 4, 5], 3)  # takes both pages
    with pytest.raises(OutOfPages):
        tiny.prefill_begin(1, [1, 2, 3, 4, 5], 3)
    tiny.allocator.check()
    assert task.shared_tokens == 0


def test_chunked_prefill_interleaves_with_decode(params):
    """A long prompt admitted mid-run is prefilled one chunk per loop
    iteration: decode steps for the running request land BETWEEN the
    newcomer's chunks (TTFT jitter capped), and both finish exact."""
    rng = np.random.default_rng(5)
    short = rng.integers(1, CFG["vocab_size"], 3).tolist()
    long = rng.integers(1, CFG["vocab_size"], 24).tolist()
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                               max_seq=40, page_size=4, prefill_chunk=8)
    res, rep = ContinuousBatchingScheduler(eng, max_new_tokens=6).run([
        Request(uid="short", prompt=short),
        Request(uid="long", prompt=long),
    ])
    by = {r.uid: r for r in res}
    assert by["short"].tokens == _naive_greedy(params, short, 6)
    assert by["long"].tokens == _naive_greedy(params, long, 6)
    # the long prompt needed 3 chunks; short decoded while they ran, so
    # short finished FIRST despite the long one being, at 24 tokens, the
    # only O(P^2) work in the run
    assert res[0].uid == "short"
    assert rep.decode_steps >= 6


def test_decode_never_writes_mid_prefill_pages(params):
    """Regression: a slot mid-chunked-prefill keeps its shared block-table
    row at SCRATCH, so interleaved decode steps (whose stale lane writes
    unconditionally at pos 0) cannot corrupt the prompt's already-written
    K/V — or a SHARED prefix page another sequence is attending over."""
    rng = np.random.default_rng(7)
    long = rng.integers(1, CFG["vocab_size"], 16).tolist()
    short = rng.integers(1, CFG["vocab_size"], 3).tolist()
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                               max_seq=32, page_size=4, prefill_chunk=8)
    # activate slot 0 with a short request so decode has work to do
    first = eng.prefill(0, short, 4)
    # begin the long prompt on slot 1 and run ONE of its two chunks
    task = eng.prefill_begin(1, long, 4)
    assert eng.prefill_step(task) is None  # chunk 1 of 2: mid-prefill
    assert (eng.block_tables[1] == 0).all(), \
        "mid-prefill slot's decode row must stay at SCRATCH"
    before = np.asarray(eng.cache["k"])[task.pages].copy()
    # decode with slot 1's lane stale at pos 0 (the corruption vector)
    eng.decode(np.array([first, 0], np.int32), np.array([3, 0], np.int32))
    after = np.asarray(eng.cache["k"])[task.pages]
    np.testing.assert_array_equal(
        before, after,
        err_msg="decode wrote into a sequence still being prefilled",
    )
    # finishing the prefill installs the row and decodes correctly
    tok = eng.prefill_step(task)
    assert tok is not None
    assert list(eng.block_tables[1][: len(task.pages)]) == task.pages
    assert tok == _naive_greedy(params, long, 1)[0]


def test_step_cap_terminates_run(params):
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                               max_seq=32, page_size=4, prefill_chunk=8)
    res, rep = ContinuousBatchingScheduler(
        eng, max_new_tokens=50, step_cap=4
    ).run([Request(uid=f"c{i}", prompt=[1, 2, 3]) for i in range(4)])
    assert rep.decode_steps == 4
    reasons = rep.finish_reasons
    assert reasons.get("step_cap", 0) >= 1
    assert reasons.get("step_cap", 0) + reasons.get("cancelled", 0) == 4
    eng.allocator.check()
    assert eng.allocator.pages_in_use == 0  # cap released everything


def test_report_queue_wait_and_prefill_compiles(params):
    """Satellites: queue_wait is its own percentile block (admission
    latency separated from prefill), and prefill_compiles counts the
    run's distinct compiled shapes — 0 on a re-run of the same shapes."""
    rng = np.random.default_rng(6)
    reqs = lambda: [  # noqa: E731
        Request(uid=f"r{i}",
                prompt=rng.integers(1, CFG["vocab_size"], 6).tolist())
        for i in range(4)
    ]
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=2,
                               max_seq=32, page_size=4, prefill_chunk=8)
    _, rep1 = ContinuousBatchingScheduler(eng, max_new_tokens=3).run(reqs())
    assert {"p50", "p99", "mean", "max"} <= set(rep1.queue_wait_s)
    assert rep1.prefill_compiles >= 1
    _, rep2 = ContinuousBatchingScheduler(eng, max_new_tokens=3).run(reqs())
    assert rep2.prefill_compiles == 0  # same shapes: nothing new compiled
    assert rep2.queue_wait_s["max"] <= rep1.queue_wait_s["max"] + 1.0

    dense = InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                            max_seq=32, prefill_attention="dense")
    _, drep1 = ContinuousBatchingScheduler(dense, max_new_tokens=3).run(
        reqs())
    assert drep1.prefill_compiles >= 1  # the 8-bucket
    _, drep2 = ContinuousBatchingScheduler(dense, max_new_tokens=3).run(
        reqs())
    assert drep2.prefill_compiles == 0


def test_paged_engine_chunk_shapes_helper(params):
    eng = PagedInferenceEngine(params, num_heads=HEADS, batch_slots=1,
                               max_seq=64, page_size=4, prefill_chunk=16)
    assert eng.chunk_shapes(40) == {16, 8}  # 16+16+8
    assert eng.chunk_shapes(16) == {16}
    assert eng.chunk_shapes(3) == {8}  # bucket floor


def test_synthetic_requests_shared_prefix():
    reqs = synthetic_requests(
        4, vocab_size=61, max_prompt=6, shared_prefix_len=8,
        rng=np.random.default_rng(0),
    )
    first = reqs[0].prompt[:8]
    assert all(r.prompt[:8] == first for r in reqs)
    assert len({tuple(r.prompt) for r in reqs}) > 1  # tails differ


# --------------------------------------------------------------------------
# CI smoke: the paged serve path end-to-end through bench.py on CPU
# --------------------------------------------------------------------------

@pytest.mark.timeout(240)
def test_bench_serve_paged_cpu_smoke():
    """Fast tier-1 smoke: bench.py --serve --kv-layout paged with a hard
    --steps-cap, so a scheduler/allocator regression surfaces on CPU
    (and, via the cap + pytest-timeout, can never hang CI)."""
    proc = subprocess.run(
        [
            sys.executable, "bench.py", "--serve", "--small",
            "--seq-len", "12", "--serve-requests", "6",
            "--batch-slots", "2", "--max-new-tokens", "4",
            "--kv-layout", "paged", "--page-size", "4",
            "--prefill-chunk", "8", "--steps-cap", "50",
        ],
        capture_output=True, text=True, timeout=220,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["kv_layout"] == "paged"
    assert line["generated_tokens"] > 0
    assert line["kv_bytes_peak"] <= line["kv_bytes"]
    assert line["hbm_bytes_per_admitted_token"] > 0
