"""One decode step in flight: the loop dispatches step n+1 before it reads
step n (ISSUE 37).

The load-bearing guarantees:

- the engine's two halves, launched twice and then read twice, give what
  ``engine.decode`` gives step by step: the second step takes its tokens
  from where the first left them on the device, and nothing the halves were
  handed may be read after they return;
- a run with a step in flight serves, request by request and to the bit,
  the tokens the same loop serves when it drives ``engine.decode`` (the
  serial turn), for the OPT engine with the prefix cache on and for a small
  ``hybrid_model`` of each kind (a window ring, a convolution state, a
  shared expert), over a run in which lanes end by budget and on EOS, slots
  are reused at once, a long prompt is chunked beside decoding lanes and
  more requests than slots queue; the allocator is whole afterwards;
- ``ServeReport.decode_steps_overlapped`` and ``decode_rows_wasted`` count
  what the run did, to the step;
- a poisoned lane is quarantined alone, ``step_cap``, a drain, a cancelled
  and an expired request end as on the serial turn;
- the depth is what the run can observe: none with the dense engine, a
  fault plan that acts on decode steps, or an engine whose ``decode`` was
  wrapped.
"""

from __future__ import annotations

import functools
import time

import jax
import numpy as np
import pytest

from distributeddeeplearning_tpu.models import hybrid_moe_transformer as hm
from distributeddeeplearning_tpu.models.pipelined_transformer import (
    init_params,
)
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    PagedInferenceEngine,
    Request,
)
from distributeddeeplearning_tpu.serve.served_model import hybrid_model
from distributeddeeplearning_tpu.utils import faults as faults_mod

VOCAB, PAGE, CHUNK, SLOTS, MAX_SEQ = 97, 4, 8, 3, 64
OPT = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64, vocab_size=VOCAB,
           max_len=MAX_SEQ)
S, F = "sliding_attention", "full_attention"
#: one small configuration a kind of per-slot state (tests/test_hybrid_moe.py,
#: test_lfm2_moe.py, test_afmoe.py hold the same shapes against a reference)
HYBRID = {
    "window-ring": {
        "vocab_size": VOCAB, "hidden_size": 32, "num_attention_heads": 8,
        "head_dim": 12, "v_head_dim": 8, "partial_rotary_factor": 0.334,
        "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
        "sliding_window": 8, "rope_theta": 5e6, "swa_rope_theta": 1e4,
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "attention_value_scale": 0.707,
        "layernorm_epsilon": 1e-5, "num_hidden_layers": 5,
        "hybrid_layer_pattern": [0, 1, 1, 0, 1],
        "moe_layer_freq": [0, 1, 1, 1, 1], "intermediate_size": 64,
        "moe_intermediate_size": 16, "n_routed_experts": 4,
        "n_routed_experts_published": 16, "experts_held": [0, 1, 2, 3],
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": None,
    },
    "conv-state": {
        "model_type": "lfm2_moe", "vocab_size": VOCAB, "hidden_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
        "conv_bias": False, "norm_eps": 1e-5, "rope_theta": 1000000,
        "num_hidden_layers": 6,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv"],
        "num_dense_layers": 2, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_experts": 8,
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
    },
    "shared-expert": {
        "model_type": "afmoe", "vocab_size": VOCAB, "hidden_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "rms_norm_eps": 1e-5, "rope_theta": 10000, "num_hidden_layers": 4,
        "layer_types": [S, S, S, F], "num_dense_layers": 2,
        "intermediate_size": 64, "moe_intermediate_size": 16,
        "num_experts": 8, "num_experts_per_tok": 3, "num_shared_experts": 1,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 16, "mup_enabled": True,
        "tie_word_embeddings": False,
    },
}
KINDS = ("opt-prefix",) + tuple(HYBRID)


@functools.lru_cache(maxsize=None)
def engine_of(kind, temperature=0.0):
    """One engine a kind for the whole file: every test leaves it with no
    slot held, and none depends on what the prefix table remembers."""
    if kind == "opt-prefix":
        return PagedInferenceEngine(
            init_params(jax.random.key(0), **OPT), num_heads=4,
            batch_slots=SLOTS, max_seq=MAX_SEQ, page_size=PAGE, num_pages=48,
            prefill_chunk=CHUNK, prefix_cache=True, temperature=temperature,
            rng=jax.random.key(7))
    spec = hm.spec_from_config(HYBRID[kind])
    return PagedInferenceEngine(
        hm.init_params(jax.random.key(0), spec, std=0.3),
        model=hybrid_model(spec), batch_slots=SLOTS, max_seq=MAX_SEQ,
        page_size=PAGE, num_pages=48, prefill_chunk=CHUNK,
        prefix_cache=False, decode_kernel="gather")


@pytest.fixture(autouse=True)
def no_fault_plan():
    faults_mod.install_plan("")
    yield
    faults_mod.install_plan("")


class serial_turn:
    """Inside, the scheduler finds an engine whose ``decode`` is not its two
    halves (a wrapper, as a fault planted on ``decode`` would be), and so
    drives ``engine.decode`` step by step: what every run did before."""

    def __init__(self, engine):
        self.engine = engine

    def __enter__(self):
        stock = self.engine.decode
        self.engine.decode = lambda tokens, pos: stock(tokens, pos)

    def __exit__(self, *exc):
        del self.engine.decode


# the first 8 tokens of every prompt are shared: two full pages for the OPT
# engine's prefix cache; one prompt is four chunks long; eight requests
# queue for three slots; the budgets end lanes at different steps
PREFIX = [11, 3, 60, 42, 8, 8, 19, 2]
TAILS = (3, 1, 25, 6, 2, 9, 4, 5)
BUDGETS = (6, 12, 5, 2, 9, 1, 7, 10)


def mixed_requests(seed=0, budgets=BUDGETS):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=f"r{i}", max_new_tokens=budget,
                prompt=PREFIX + rng.integers(1, VOCAB, tail).tolist())
        for i, (tail, budget) in enumerate(zip(TAILS, budgets))
    ]


def serve(engine, requests, *, eos_id=None, in_flight=True, **run_kw):
    """(tokens by uid, finish reason by uid, report); the allocator has to be
    whole and empty afterwards whatever the run did."""
    scheduler_kw = {k: run_kw.pop(k) for k in ("step_cap",) if k in run_kw}
    scheduler = ContinuousBatchingScheduler(
        engine, eos_id=eos_id, **scheduler_kw)
    if "scheduler_hook" in run_kw:
        run_kw.pop("scheduler_hook")(scheduler)
    if in_flight:
        results, report = scheduler.run(requests, **run_kw)
    else:
        with serial_turn(engine):
            results, report = scheduler.run(requests, **run_kw)
    engine.allocator.check()
    assert engine.allocator.pages_in_use == 0
    assert len(results) == len(requests)
    return ({r.uid: r.tokens for r in results},
            {r.uid: r.finish_reason for r in results}, report)


def an_eos_that_ends_lanes_mid_run(tokens):
    """A token id that some request generates strictly inside its stream (so
    a lane ends on it with a row in flight), and is no request's first."""
    firsts = {toks[0] for toks in tokens.values()}
    for toks in tokens.values():
        for tok in toks[1:-1]:
            if tok not in firsts:
                return tok
    raise AssertionError("no token fits: change the seed")


# --- the engine's two halves --------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_two_steps_launched_then_read_equal_decode_step_by_step(kind):
    engine = engine_of(kind)
    prompts = {0: PREFIX + [5, 6, 7], 2: PREFIX + [9]}

    def prefilled():
        firsts = {slot: engine.prefill(slot, prompt, 4)
                  for slot, prompt in prompts.items()}
        tokens = np.zeros(SLOTS, np.int32)
        pos = np.zeros(SLOTS, np.int32)
        for slot, prompt in prompts.items():
            tokens[slot], pos[slot] = firsts[slot], len(prompt)
        return tokens, pos

    def released():
        for slot in prompts:
            engine.release(slot)

    tokens, pos = prefilled()
    one = engine.decode(tokens, pos)
    finite_one = engine.last_finite.copy()
    for slot in prompts:
        tokens[slot], pos[slot] = one[slot], pos[slot] + 1
    two = engine.decode(tokens, pos)
    released()

    tokens, pos = prefilled()
    fresh = np.ones(SLOTS, bool)
    first = engine.decode_dispatch(tokens, pos, fresh, None)
    # the caller's buffers are its own again: the second step's tokens are
    # the device's, its positions one on, and what the first was handed is
    # overwritten before it is read
    tokens[:] = VOCAB - 1
    pos[list(prompts)] += 1
    fresh[list(prompts)] = False
    second = engine.decode_dispatch(tokens, pos, fresh, None)
    pos[:] = 0
    fresh[:] = True
    got_one = engine.decode_fetch(first)
    assert (engine.last_finite == finite_one).all()
    got_two = engine.decode_fetch(second)
    released()
    for slot in prompts:
        assert (got_one[slot], got_two[slot]) == (one[slot], two[slot])


def test_a_lane_without_a_row_is_uploaded_as_a_released_slot_is():
    """``rows`` false: the scratch row, whatever the slot's table says, so
    the lane's own pages are not written by a step it takes no part in."""
    engine = engine_of("opt-prefix")
    prompt = PREFIX + [4, 4]
    first = engine.prefill(1, prompt, 4)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[1], pos[1] = first, len(prompt)
    pages = np.asarray(engine._slot_pages[1])
    before = {name: np.asarray(leaf[pages]) for name, leaf in
              engine.cache.items()}
    rows = np.zeros(SLOTS, bool)
    engine.decode_fetch(engine.decode_dispatch(tokens, pos, None, rows))
    for name, leaf in engine.cache.items():
        assert (np.asarray(leaf[pages]) == before[name]).all(), name
    want = engine.decode(tokens, pos)  # and with its row it writes them
    assert any((np.asarray(leaf[pages]) != before[name]).any()
               for name, leaf in engine.cache.items())
    engine.release(1)
    again = engine.prefill(1, prompt, 4)
    tokens[1] = again
    assert engine.decode(tokens, pos)[1] == want[1]
    engine.release(1)


# --- the loop: the same tokens, request by request ----------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_step_in_flight_serves_the_serial_turns_tokens(kind):
    engine = engine_of(kind)
    plain, _, _ = serve(engine, mixed_requests(), in_flight=False)
    eos = an_eos_that_ends_lanes_mid_run(plain)

    want, want_reasons, serial = serve(
        engine, mixed_requests(), eos_id=eos, in_flight=False)
    got, reasons, report = serve(engine, mixed_requests(), eos_id=eos)
    assert got == want and reasons == want_reasons
    assert "eos" in reasons.values() and "length" in reasons.values()
    for uid, toks in got.items():  # cut at the EOS, and nowhere else
        assert toks == plain[uid][: len(toks)]
        assert eos not in toks[:-1]

    assert serial.decode_steps_overlapped == serial.decode_rows_wasted == 0
    assert report.decode_steps_overlapped >= 0.7 * report.decode_steps
    # one row a lane that ended on EOS past its first decode step... and
    # every such lane was still inside its budget
    late = sum(1 for uid, toks in got.items()
               if reasons[uid] == "eos" and 1 < len(toks))
    assert 1 <= report.decode_rows_wasted <= late
    assert report.generated_tokens == serial.generated_tokens
    if kind == "opt-prefix":
        assert report.prefix_hit_rate > 0


@pytest.mark.parametrize("kind", ("opt-prefix", "conv-state"))
def test_tokens_equal_decode_driven_by_hand(kind):
    """Against no scheduler at all: each request alone in a lane of the same
    engine, ``engine.decode`` step by step."""
    engine = engine_of(kind)
    got, _, _ = serve(engine, mixed_requests(seed=3))
    for req in mixed_requests(seed=3):
        tok = engine.prefill(1, req.prompt, req.max_new_tokens)
        alone = [tok]
        tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        while len(alone) < req.max_new_tokens:
            tokens[1], pos[1] = alone[-1], len(req.prompt) + len(alone) - 1
            alone.append(int(engine.decode(tokens, pos)[1]))
        engine.release(1)
        assert got[req.uid] == alone, req.uid


def test_sampled_tokens_follow_the_order_of_dispatch():
    """With a temperature the per-call key counts final chunks and decode
    steps as they are dispatched: one request's calls come in the same order
    with a step in flight, so it draws the same tokens."""
    engine = engine_of("opt-prefix", temperature=0.9)
    request = [Request(uid="r", prompt=PREFIX + [5], max_new_tokens=9)]
    engine._sample_step = 0
    want, _, _ = serve(engine, request, in_flight=False)
    engine._sample_step = 0
    got, _, report = serve(engine, request)
    assert got == want and report.decode_steps_overlapped == 7
    engine._sample_step = 0
    greedy, _, _ = serve(engine_of("opt-prefix"), request)
    assert got != greedy  # it did sample


# --- the counters, to the step -------------------------------------------------

def test_counters_count_what_the_run_did():
    engine = engine_of("opt-prefix")
    request = [Request(uid="r", prompt=PREFIX + [5], max_new_tokens=5)]
    tokens, _, report = serve(engine, request)
    # the first token is the prefill's; four decode steps: the first goes
    # out with nothing in flight, the other three beside the one before;
    # the lane's end is known by count, so no fifth step is dispatched
    assert len(tokens["r"]) == 5
    assert (report.decode_steps, report.decode_steps_overlapped,
            report.decode_rows_wasted) == (4, 3, 0)

    # EOS as the third token, the second decode step's: the third step is
    # in flight when it is read, and its row is computed and dropped
    eos = tokens["r"][2]
    assert eos not in tokens["r"][:2]
    cut, reasons, report = serve(engine, request, eos_id=eos)
    assert cut["r"] == tokens["r"][:3] and reasons["r"] == "eos"
    assert (report.decode_steps, report.decode_steps_overlapped,
            report.decode_rows_wasted) == (3, 2, 1)
    assert report.to_dict()["decode_rows_wasted"] == 1


@pytest.mark.parametrize("why", ["dense", "fault-plan", "wrapped-decode"])
def test_no_step_in_flight_where_the_run_cannot_hold_one(why):
    requests = [Request(uid=f"r{i}", prompt=PREFIX + [i + 1],
                        max_new_tokens=6) for i in range(4)]
    if why == "dense":
        engine = InferenceEngine(
            init_params(jax.random.key(0), **OPT), num_heads=4,
            batch_slots=SLOTS, max_seq=MAX_SEQ)
        _, report = ContinuousBatchingScheduler(engine, eos_id=None).run(
            requests)
    elif why == "fault-plan":
        faults_mod.install_plan("decode_stall@2:secs=0.001")
        _, _, report = serve(engine_of("opt-prefix"), requests)
    else:
        _, _, report = serve(engine_of("opt-prefix"), requests,
                             in_flight=False)
    assert report.decode_steps > 0
    assert report.decode_steps_overlapped == report.decode_rows_wasted == 0


def test_a_plan_that_only_rejects_admissions_keeps_the_step_in_flight():
    faults_mod.install_plan("reject_admit@2")
    requests = [Request(uid=f"r{i}", prompt=PREFIX + [i + 1],
                        max_new_tokens=6) for i in range(3)]
    _, reasons, report = serve(engine_of("opt-prefix"), requests)
    assert sorted(reasons.values()) == ["length", "length", "shed"]
    assert report.decode_steps_overlapped > 0


# --- what takes a lane away by another road -----------------------------------

def _same_length_requests(n=4, new=10):
    rng = np.random.default_rng(5)
    return [Request(uid=f"r{i}", max_new_tokens=new,
                    prompt=PREFIX + rng.integers(1, VOCAB, 2).tolist())
            for i in range(n)]


@pytest.mark.parametrize("kind", KINDS)
def test_a_poisoned_lane_is_quarantined_alone(kind):
    """NaN into one lane's decode-written K while a step is in flight (no
    fault plan: the step stays in flight): the verdict comes a read late,
    the lane fails alone with a row dropped, the scrub leaves the pool
    clean for the next occupant, the neighbours' tokens are unchanged."""
    engine = engine_of(kind)
    clean, _, _ = serve(engine, _same_length_requests())
    prompt_len = len(PREFIX) + 2

    def poison(step):
        if step == 3:
            # the first request's slot: it has decoded since step 1
            engine.poison_slot(max(engine._slot_pages), prompt_len)

    got, reasons, report = serve(
        engine, _same_length_requests(), on_step=poison)
    failed = [uid for uid, why in reasons.items() if why == "error"]
    assert len(failed) == 1 and report.quarantined == 1
    assert got[failed[0]] == clean[failed[0]][: len(got[failed[0]])]
    for uid in clean:
        if uid not in failed:
            assert got[uid] == clean[uid] and reasons[uid] == "length"
    assert report.decode_rows_wasted >= 1
    again, _, _ = serve(engine, _same_length_requests())
    assert again == clean  # nothing of the NaN is left in the pool


def test_a_planned_decode_nan_runs_the_serial_turn_and_quarantines():
    engine = engine_of("opt-prefix")
    clean, _, _ = serve(engine, _same_length_requests())
    faults_mod.install_plan("decode_nan@3")
    got, reasons, report = serve(engine, _same_length_requests())
    failed = [uid for uid, why in reasons.items() if why == "error"]
    assert len(failed) == 1 and report.quarantined == 1
    assert report.decode_steps_overlapped == 0
    assert all(got[uid] == clean[uid] for uid in clean if uid not in failed)


@pytest.mark.parametrize("kind", ("opt-prefix", "window-ring"))
def test_step_cap_ends_as_the_serial_turn(kind):
    engine = engine_of(kind)
    want, want_reasons, serial = serve(
        engine, mixed_requests(), step_cap=5, in_flight=False)
    got, reasons, report = serve(engine, mixed_requests(), step_cap=5)
    assert report.decode_steps == serial.decode_steps == 5
    assert report.decode_rows_wasted == 0  # no step past the cap went out
    assert "step_cap" in reasons.values()
    # a slot freed at a read is refilled a turn later than on the serial
    # turn, so which queued request got in before the cap may differ: what
    # each request was served is the same stream, cut where its run was
    plain, _, _ = serve(engine, mixed_requests(), in_flight=False)
    for uid, toks in got.items():
        assert toks == plain[uid][: len(toks)]
        assert reasons[uid] in ("length", "step_cap", "cancelled")
    assert sum(map(len, want.values())) > 0
    assert set(want_reasons.values()) <= {"length", "step_cap", "cancelled"}


@pytest.mark.parametrize("kind", ("opt-prefix", "shared-expert"))
def test_a_drain_finishes_what_decodes_and_returns_what_queues(kind):
    engine = engine_of(kind)
    plain, _, _ = serve(engine, mixed_requests(), in_flight=False)
    emitted = []

    got, reasons, report = serve(
        engine, mixed_requests(),
        on_token=lambda uid, tok: emitted.append(uid),
        should_drain=lambda: len(emitted) >= 8)
    assert report.drained
    assert set(reasons.values()) == {"length", "preempted"}
    for uid, why in reasons.items():
        assert got[uid] == (plain[uid] if why == "length" else [])
    # every token streamed is in a result: the step in flight at the drain
    # was read and emitted, not dropped
    assert len(emitted) == sum(map(len, got.values()))


@pytest.mark.parametrize("how", ["cancelled", "deadline"])
@pytest.mark.parametrize("kind", ("opt-prefix", "conv-state"))
def test_a_request_taken_away_mid_decode_ends_as_before(kind, how):
    engine = engine_of(kind)
    requests = _same_length_requests(n=3, new=12)
    clean, _, _ = serve(engine, requests)
    holder = {}

    def on_token(uid, tok):
        if uid != "r1":
            return
        holder["n"] = holder.get("n", 0) + 1
        if how == "cancelled" and holder["n"] == 3:
            holder["scheduler"].request_cancel("r1")
        if how == "deadline":
            time.sleep(0.2)  # three tokens in, its half second is gone

    requests = _same_length_requests(n=3, new=12)
    if how == "deadline":
        requests[1].deadline_s = 0.5
    got, reasons, report = serve(
        engine, requests, on_token=on_token,
        scheduler_hook=lambda s: holder.update(scheduler=s))
    assert reasons == {"r0": "length", "r1": how, "r2": "length"}
    assert 1 <= len(got["r1"]) < 12
    assert got["r1"] == clean["r1"][: len(got["r1"])]
    assert got["r0"] == clean["r0"] and got["r2"] == clean["r2"]
    # it left between a dispatch and that step's read: one row dropped
    assert report.decode_rows_wasted == 1


def test_a_decode_that_raises_requeues_once_and_serves_the_same_tokens():
    engine = engine_of("opt-prefix")
    requests = _same_length_requests(n=3, new=8)
    clean, _, _ = serve(engine, requests)
    stock, calls = engine.decode_dispatch, [0]

    def flaky(*args):
        calls[0] += 1
        if calls[0] == 4:
            raise RuntimeError("collective died")
        return stock(*args)

    engine.decode_dispatch = flaky
    try:
        got, reasons, report = serve(engine, _same_length_requests(n=3, new=8))
    finally:
        del engine.decode_dispatch
    assert set(reasons.values()) == {"length"}
    assert report.decode_retries >= 2 and report.errors == 0
    assert got == clean  # what the step in flight had computed was streamed


# --- the program: the same one, two small arguments more -----------------------

def test_the_decode_program_sets_nothing_pool_sized_aside():
    """Feeding the step its own last tokens is a ``where`` over [slots]:
    what the compiled program keeps beside its arguments does not grow with
    the pool, and the second step reuses the first step's executable."""
    import jax.numpy as jnp

    def temporaries(num_pages):
        engine = PagedInferenceEngine(
            init_params(jax.random.key(0), **OPT), num_heads=4,
            batch_slots=SLOTS, max_seq=MAX_SEQ, page_size=PAGE,
            num_pages=num_pages, prefill_chunk=CHUNK)
        shape = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        compiled = engine._decode_jit._fn.lower(
            shape(engine.params), shape(engine.cache), i32(SLOTS), i32(SLOTS),
            i32(SLOTS, engine.blocks_per_slot), i32(), i32(SLOTS),
            jax.ShapeDtypeStruct((SLOTS,), jnp.bool_), False).compile()
        return compiled.memory_analysis().temp_size_in_bytes, engine.kv_bytes()

    small, _ = temporaries(16)
    large, pool_bytes = temporaries(256)
    assert large == small and large < pool_bytes / 4

    engine = engine_of("opt-prefix")
    serve(engine, mixed_requests())
    before = engine._decode_jit._cache_size()
    serve(engine, mixed_requests(seed=1))
    serve(engine, mixed_requests(seed=2), in_flight=False)
    assert engine._decode_jit._cache_size() == before == 1
