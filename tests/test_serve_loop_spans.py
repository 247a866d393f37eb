"""The serve loop accounts for its own time (ISSUE 38).

The load-bearing guarantees:

- ``serve/idle`` covers each stretch in which a live loop holds nothing,
  one span a stretch however many polls it makes, from its first poll to
  the end of the poll that brings a request; ``serve/turn`` covers each
  turn that holds a request; the two never overlap and, at depth 0, tile
  the loop;
- every turn that dispatches a decode step carries the loop's number for
  it and its live lanes; the engine's spans of one step carry the engine's
  number for it, so that with a step in flight ``decode_fetch(step=n)``
  comes after ``decode_dispatch`` of the step after n, on both engines;
- a chunked request gives exactly one ``serve/engine.first_token_fetch``,
  with its uid, inside its final ``serve/prefill_chunk``;
- a collection is one ``host/gc`` span (``generation``, ``collected``) while
  the process tracer records, enabled or following a capture, and nothing
  otherwise.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    init_params,
)
from distributeddeeplearning_tpu.obs import trace as trace_mod
from distributeddeeplearning_tpu.obs.trace import Tracer
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    PagedInferenceEngine,
    Request,
)

CFG = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64, vocab_size=61,
           max_len=64)
QUIET_POLLS = 30


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), **CFG)


@pytest.fixture
def tracer():
    """A fresh, enabled process tracer, disabled again afterwards."""
    fresh = trace_mod.set_tracer(Tracer(enabled=True))
    yield fresh
    trace_mod.set_tracer(Tracer(enabled=False))


def _paged(params, *, chunk=8):
    return PagedInferenceEngine(
        params, num_heads=CFG["num_heads"], batch_slots=3, max_seq=64,
        page_size=8, num_pages=40, prefill_chunk=chunk)


def _requests(n, *, prompt_len=10, new=8, seed=0, prefix="r"):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=f"{prefix}{i}", max_new_tokens=new,
                prompt=rng.integers(1, CFG["vocab_size"], prompt_len).tolist())
        for i in range(n)
    ]


def _spans(tracer, name=None):
    return sorted((e for e in tracer.events if e["ph"] == "X"
                   and (name is None or e["name"] == name)),
                  key=lambda e: e["ts"])


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def _run_quiet_then_deliver(scheduler, tracer):
    """A live source: a first batch, then quiet polls once it is done, then
    a second batch, then closed once that is done. Returns the time of the
    poll that brought the second batch, on the tracer's clock (us)."""
    first, second = _requests(2), _requests(2, seed=1, prefix="s")
    done, state = [], {"polls": 0, "quiet": 0, "second_at": None}

    def poll():
        state["polls"] += 1
        if state["polls"] == 1:
            return list(first)
        if len(done) < len(first):
            return []
        if state["quiet"] < QUIET_POLLS:
            state["quiet"] += 1
            return []
        if state["second_at"] is None:
            state["second_at"] = 1e6 * (
                time.perf_counter() - tracer.epoch_perf_s)
            return list(second)
        return None if len(done) == len(first) + len(second) else []

    results, report = scheduler.run([], poll=poll, on_complete=done.append)
    assert sorted(r.uid for r in results) == ["r0", "r1", "s0", "s1"]
    return state["second_at"], report


def test_a_quiet_stretch_is_one_idle_span_and_turns_tile_the_rest(
    params, tracer,
):
    scheduler = ContinuousBatchingScheduler(_paged(params), eos_id=None)
    scheduler.run(_requests(2, new=2, seed=5))  # compiles, before the spans
    tracer.clear()
    second_at, report = _run_quiet_then_deliver(scheduler, tracer)
    idle, turns = _spans(tracer, "serve/idle"), _spans(tracer, "serve/turn")
    # three stretches hold nothing: before the first poll's batch, the quiet
    # one, and the poll that found the source closed; one span each
    assert len(idle) == 3
    quiet = max(idle, key=lambda e: e["dur"])
    assert quiet["ts"] < second_at < quiet["ts"] + quiet["dur"]
    assert not any(_inside(e, quiet) for e in _spans(tracer, "serve/poll"))
    assert all(e["args"]["depth"] == 0 for e in idle + turns)
    # never overlapping, and between them no more than the loop's own
    # step from one to the next (or a collection at depth 0)
    tiles = sorted(idle + turns, key=lambda e: e["ts"])
    for a, b in zip(tiles, tiles[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-3
    wall = tiles[-1]["ts"] + tiles[-1]["dur"] - tiles[0]["ts"]
    covered = sum(e["dur"] for e in tiles)
    gc_top = sum(e["dur"] for e in _spans(tracer, "host/gc")
                 if e["args"]["depth"] == 0)
    assert covered + gc_top >= 0.99 * wall, (covered, gc_top, wall)
    # every span of the loop but the collections sits inside one of them
    for e in _spans(tracer):
        if e["name"].startswith("serve/") and e["args"]["depth"] >= 1:
            assert any(_inside(e, t) for t in tiles), e
    assert report.decode_steps > 0


def test_a_disabled_tracer_records_no_loop_span(params):
    trace_mod.set_tracer(Tracer(enabled=False))
    scheduler = ContinuousBatchingScheduler(_paged(params), eos_id=None)
    _run_quiet_then_deliver(scheduler, trace_mod.get_tracer())
    assert trace_mod.get_tracer().events == []


def test_a_capture_that_starts_in_a_quiet_stretch_records_the_rest_of_it(
    params, tmp_path,
):
    """The benchmark's window opens inside a poll: a stretch that began
    before it is one span from there to the poll that brings a request."""
    quiet = trace_mod.set_tracer(Tracer(enabled=False))
    scheduler = ContinuousBatchingScheduler(_paged(params), eos_id=None)
    scheduler.run(_requests(2, new=2, seed=5))
    batch, done, state = _requests(2, new=4), [], {"polls": 0}

    def poll():
        state["polls"] += 1
        if state["polls"] == 5:
            jax.profiler.start_trace(str(tmp_path))
            state["started"] = time.perf_counter()
        if state["polls"] == 20:
            state["delivered"] = time.perf_counter()
            return list(batch)
        return None if len(done) == len(batch) else []

    try:
        scheduler.run([], poll=poll, on_complete=done.append)
    finally:
        jax.profiler.stop_trace()
        trace_mod.set_tracer(Tracer(enabled=False))
    idle = _spans(quiet, "serve/idle")
    assert len(idle) >= 1
    first = idle[0]
    start = quiet.epoch_perf_s + 1e-6 * first["ts"]
    end = start + 1e-6 * first["dur"]
    assert state["started"] < start < end
    assert end > state["delivered"]
    assert _spans(quiet, "serve/turn")


def _engine_steps(tracer, name):
    return [e["args"]["step"] for e in _spans(tracer, name)]


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_turns_and_engine_spans_carry_their_step(params, tracer, layout):
    if layout == "paged":
        engine = _paged(params)
    else:
        engine = InferenceEngine(params, num_heads=CFG["num_heads"],
                                 batch_slots=3, max_seq=64)
    scheduler = ContinuousBatchingScheduler(engine, eos_id=None)
    scheduler.run(_requests(2, new=2, seed=5))
    tracer.clear()
    _, report = scheduler.run(_requests(5, new=9))
    turns = _spans(tracer, "serve/turn")
    dispatches = _spans(tracer, "serve/engine.decode_dispatch")
    stepped = [t for t in turns if t["args"]["step"] != -1]
    # the loop's numbers, one a dispatching turn, each holding its dispatch
    assert [t["args"]["step"] for t in stepped] == list(
        range(1, report.decode_steps + 1))
    assert all(1 <= t["args"]["live"] <= 3 for t in stepped)
    assert all(t["args"]["live"] == 0 for t in turns if t not in stepped)
    for t in turns:
        held = [d for d in dispatches if _inside(d, t)]
        assert len(held) == (t in stepped)
    # the engine's numbers: one step, one number on all three spans
    steps = _engine_steps(tracer, "serve/engine.decode_dispatch")
    assert len(set(steps)) == len(steps) == report.decode_steps
    assert steps == sorted(steps)
    assert _engine_steps(tracer, "serve/engine.decode_upload") == steps
    assert sorted(_engine_steps(tracer, "serve/engine.decode_fetch")) == steps
    # a fetch names the step it reads: with a step in flight that is the
    # step before the one its turn dispatched
    dispatch_end = {e["args"]["step"]: e["ts"] + e["dur"] for e in dispatches}
    fetch_start = {e["args"]["step"]: e["ts"]
                   for e in _spans(tracer, "serve/engine.decode_fetch")}
    read_late = sum(fetch_start[n] > dispatch_end[m]
                    for n, m in zip(steps, steps[1:]))
    assert read_late == report.decode_steps_overlapped
    if layout == "paged":
        assert report.decode_steps_overlapped >= report.decode_steps // 2
    else:
        assert report.decode_steps_overlapped == 0


def test_a_chunked_request_gives_one_first_token_fetch(params, tracer):
    scheduler = ContinuousBatchingScheduler(_paged(params), eos_id=None)
    scheduler.run(_requests(1, prompt_len=30, new=2, seed=5))
    tracer.clear()
    (result,), _ = scheduler.run(_requests(1, prompt_len=30, new=4))
    assert result.finish_reason == "length"
    chunks = _spans(tracer, "serve/prefill_chunk")
    reads = _spans(tracer, "serve/engine.first_token_fetch")
    assert len(chunks) == 4  # 30 tokens in chunks of 8
    assert len(reads) == 1
    (read,) = reads
    assert read["args"]["uid"] == "r0"
    assert _inside(read, chunks[-1])
    assert read["args"]["depth"] == chunks[-1]["args"]["depth"] + 1


def test_the_dense_prefill_read_is_a_span_inside_serve_prefill(params, tracer):
    engine = InferenceEngine(params, num_heads=CFG["num_heads"],
                             batch_slots=2, max_seq=64)
    ContinuousBatchingScheduler(engine, eos_id=None).run(_requests(2, new=3))
    prefills = _spans(tracer, "serve/prefill")
    reads = _spans(tracer, "serve/engine.first_token_fetch")
    assert len(reads) == len(prefills) == 2
    for read, prefill in zip(reads, prefills):
        assert _inside(read, prefill)


def _collections(tracer):
    return [e for e in tracer.events if e["name"] == "host/gc"]


def test_a_collection_is_one_host_gc_span_while_the_tracer_records(tracer):
    tracer.clear()
    with tracer.span("outer"):
        gc.collect()
    full = [e for e in _collections(tracer) if e["args"]["generation"] == 2]
    assert len(full) == 1
    (span,) = full
    assert span["args"]["collected"] >= 0
    assert span["args"]["depth"] == 1  # inside the span it landed in
    trace_mod.set_tracer(Tracer(enabled=False))
    gc.collect()
    assert trace_mod.get_tracer().events == []


def test_a_collection_in_a_capture_is_a_host_gc_span(tmp_path):
    quiet = trace_mod.set_tracer(Tracer(enabled=False))
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with quiet.span("bind"):  # a span binds the capture probe
                pass
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        gc.collect()
        assert [e["args"]["generation"] for e in _collections(quiet)
                if e["args"]["generation"] == 2] == [2]
    finally:
        trace_mod.set_tracer(Tracer(enabled=False))


def test_the_gc_hook_is_installed_once_and_ignores_a_stand_in_tracer():
    hooks = [h for h in gc.callbacks if h is trace_mod._on_gc]
    assert len(hooks) == 1
    trace_mod.set_tracer(object())  # what the reader tests put in its place
    try:
        gc.collect()
    finally:
        trace_mod.set_tracer(Tracer(enabled=False))
    assert trace_mod._gc_span is None
