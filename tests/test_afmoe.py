"""Window layers whose ring is longer than a prefill chunk beside full layers
with no position signal, a gated attention output, a norm after every
operator, a scaled embedding and an always-on shared expert beside the routed
ones, on the paged serving path: the ``afmoe`` values of
``models/hybrid_moe_transformer.HybridSpec``, against the plain reference the
benchmark keeps (``benchmarks/families/afmoe_reference.py``, which imports
nothing of the program).

The tiny size has every mechanism present: ``sliding, sliding, sliding,
full`` twice, the first two layers with a dense FFN; 4 query / 2 KV heads of 8
with a learned norm a head; a **window of 16 over chunks of 8 and pages of
4** (the ring is two chunks and four pages long, as 2,048 is four chunks and
sixteen pages at the published size); 8 experts, 3 a token, with a correction
bias that changes who is chosen, and one shared expert; an untied head.

Tolerance: everything runs in float32 on the CPU, where the program and the
reference differ only in the order of float32 sums: ``ATOL`` 2e-4 on logits
of spread 1.5 (the widest read over the served prompts below is 4e-5, through
eight layers).  Every mechanism left out moves the logits by 1e-2 or more
(the tests below that leave one out read so).
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models import hybrid_moe_transformer as hm
from distributeddeeplearning_tpu.serve import kv_cache
from distributeddeeplearning_tpu.serve.engine import PagedInferenceEngine
from distributeddeeplearning_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from distributeddeeplearning_tpu.serve.served_model import (
    FEATURES,
    Refused,
    hybrid_model,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = os.path.join(ROOT, "benchmarks", "families")
if FAMILIES not in sys.path:
    sys.path.insert(0, FAMILIES)
ref = importlib.import_module("afmoe_reference")
family_weights = importlib.import_module("afmoe_weights")

S, F = "sliding_attention", "full_attention"
TINY = {
    "model_type": "afmoe", "vocab_size": 97, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "num_hidden_layers": 8,
    "layer_types": [S, S, S, F] * 2, "num_dense_layers": 2,
    "intermediate_size": 64, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 3, "num_shared_experts": 1, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 16,
    "mup_enabled": True, "tie_word_embeddings": False,
}
SPEC = hm.spec_from_config(TINY)
ARCH = ref.arch_of(TINY)
ATOL = 2e-4
MOVED = 1e-2  # what leaving a mechanism out moves the logits by, at least
PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
D, W = TINY["hidden_size"], TINY["sliding_window"]


def make_params(seed=0, spec=SPEC, std=0.3):
    params = hm.init_params(jax.random.key(seed), spec, std=std)
    keys = iter(jax.random.split(jax.random.key(seed + 1000), 128))
    for p in params["layers"]:
        if "router_bias" in p:  # a correction bias that changes who is chosen
            p["router_bias"] = p["router_bias"] * 0.5
        for name in ("q_norm", "k_norm", "ln1_post", "ln2_post"):
            if name in p:  # norm scales that are not all 1
                p[name] = 1.0 + 0.3 * jax.random.normal(next(keys), p[name].shape)
    return params


def make_engine(params, spec=SPEC, *, kernel="gather", slots=SLOTS,
                max_seq=MAX_SEQ, pages=48, **kw):
    return PagedInferenceEngine(
        params, model=hybrid_model(spec), batch_slots=slots, max_seq=max_seq,
        page_size=PAGE, num_pages=pages, prefill_chunk=CHUNK,
        prefix_cache=False, capture_logits=True, decode_kernel=kernel, **kw)


@functools.lru_cache(maxsize=None)
def shared(seed=0, kernel="gather", window=W):
    """One engine a (weights, kernel, window), used by one test after another
    with NO scrub between them: a slot's ring is whatever its last user
    left."""
    spec = dataclasses.replace(SPEC, window=window)
    params = make_params(seed, spec)
    return params, make_engine(params, spec, kernel=kernel)


def tokens_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def serve_alone(eng, prompt, steps, *, slot=1):
    """Prefill `prompt` in chunks, then `steps` greedy decode steps, in
    `slot`, which is released afterwards: (the sequence, the logits of every
    position from the prompt's last on)."""
    task = eng.prefill_begin(slot, list(prompt), steps + 1)
    tok = None
    while tok is None:
        tok = eng.prefill_step(task)
    seq, rows = list(prompt) + [tok], [eng.last_prefill_logits]
    tokens = np.zeros(eng.batch_slots, np.int32)
    pos = np.zeros(eng.batch_slots, np.int32)
    for _ in range(steps):
        tokens[slot], pos[slot] = seq[-1], len(seq) - 1
        out = eng.decode(tokens, pos)
        rows.append(eng.last_logits[slot])
        seq.append(int(out[slot]))
    eng.release(slot)
    return seq, np.stack(rows)


def reference_rows(params, seq, length, arch=ARCH):
    want = ref.forward(params, jnp.asarray(seq[:-1]), arch)
    return np.asarray(want)[length - 1:]


# -- the spec and the cache ---------------------------------------------------------


def test_the_spec_reads_the_published_keys():
    assert SPEC.attn_kinds == (hm.WINDOW, hm.WINDOW, hm.WINDOW, hm.FULL) * 2
    assert SPEC.ffn_kinds == (hm.DENSE,) * 2 + (hm.EXPERTS,) * 6
    assert (SPEC.window, SPEC.kv_heads_full, SPEC.kv_heads_window) == (16, 2, 2)
    assert (SPEC.k_dim, SPEC.v_dim, SPEC.rotary_dim) == (8, 8, 8)
    assert SPEC.rotates(hm.WINDOW) and not SPEC.rotates(hm.FULL)
    assert SPEC.qk_norm and SPEC.output_gate and SPEC.post_norms
    assert not SPEC.tied_head and not SPEC.sink_full and not SPEC.sink_window
    assert SPEC.shared_width == 16 and SPEC.embed_scale == pytest.approx(32 ** 0.5)
    assert (SPEC.norm_topk, SPEC.routed_scale, SPEC.topk_eps) == (True, 2.826, 1e-20)
    # a file that holds a share: the router keeps the published width
    cut = hm.spec_from_config({
        **TINY, "num_experts": 2, "num_experts_published": 8,
        "experts_held": [4, 5], "num_hidden_layers": 5,
        "layers_kept": [0, 4, 5, 6, 7], "mup_enabled": False})
    assert cut.num_experts == 8 and cut.experts_held == (4, 5)
    assert cut.attn_kinds == (hm.WINDOW,) * 4 + (hm.FULL,)
    assert cut.ffn_kinds == (hm.DENSE,) + (hm.EXPERTS,) * 4
    assert cut.embed_scale == 1.0
    with pytest.raises(ValueError, match="layers_kept"):
        hm.spec_from_config({**TINY, "layers_kept": [0, 1]})


NEW_DEFAULTS = {"shared_width": 0, "output_gate": False, "post_norms": False,
                "rotate_full": True, "embed_scale": 1.0}
#: the specs the two accepted files gave on the parent commit, field by field
AS_BEFORE = {
    "mimo-v2-flash": dict(
        vocab_size=19072, d_model=4096, num_q_heads=64, k_dim=192, v_dim=128,
        rotary_dim=64, kv_heads_full=4, kv_heads_window=8, window=128,
        theta_full=5000000.0, theta_window=10000.0, sink_full=False,
        sink_window=True, value_scale=0.707, eps=1e-05,
        attn_kinds=(0, 1, 1, 1, 1, 1, 0), ffn_kinds=(0, 1, 1, 1, 1, 1, 1),
        d_ff=16384, d_expert=2048, num_experts=256, experts_per_token=8,
        experts_held=tuple(range(16)), norm_topk=True, routed_scale=1.0,
        topk_eps=0.0, conv_taps=0, qk_norm=False, tied_head=False),
    "lfm2-8b-a1b": dict(
        vocab_size=65536, d_model=2048, num_q_heads=32, k_dim=64, v_dim=64,
        rotary_dim=64, kv_heads_full=8, kv_heads_window=8, window=0,
        theta_full=1000000.0, theta_window=1000000.0, sink_full=False,
        sink_window=False, value_scale=1.0, eps=1e-05,
        attn_kinds=(2, 2) + (0, 2, 2, 2) * 3, ffn_kinds=(0, 0) + (1,) * 12,
        d_ff=7168, d_expert=1792, num_experts=32, experts_per_token=4,
        experts_held=tuple(range(32)), norm_topk=True, routed_scale=1.0,
        topk_eps=1e-06, conv_taps=3, qk_norm=True, tied_head=True),
}


@pytest.mark.parametrize("name", sorted(AS_BEFORE))
def test_the_accepted_configurations_read_as_before(name):
    """Their files give the spec they gave: every old field as it was, every
    new field at the default that traces the operations the parent traced (a
    file with `layer_types` and no `model_type` of `afmoe` is still an
    `lfm2_moe` file, one with neither still a `mimo_v2` file)."""
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        cfg = json.load(f)
    spec = hm.spec_from_config(cfg)
    assert set(AS_BEFORE[name]) | set(NEW_DEFAULTS) == {
        f.name for f in dataclasses.fields(hm.HybridSpec)}
    assert spec == hm.HybridSpec(**AS_BEFORE[name])
    assert {k: getattr(spec, k) for k in NEW_DEFAULTS} == NEW_DEFAULTS
    nameless = {k: v for k, v in cfg.items() if k != "model_type"}
    assert hm.spec_from_config(nameless) == spec
    shapes = hm.layer_shapes(spec, spec.num_layers - 1)
    assert not {"w_gate", "ln1_post", "ln2_post", "shared_wg"} & set(shapes)


def test_the_family_s_shapes_are_the_program_s():
    shapes = family_weights.leaf_shapes(TINY)
    for layer in range(SPEC.num_layers):
        mine = {k[2]: v for k, v in shapes.items()
                if k[0] == "layers" and k[1] == layer}
        assert mine == hm.layer_shapes(SPEC, layer)
    assert shapes[("head",)] == (D, 97) and shapes[("embed",)] == (97, D)
    assert set(family_weights.NORM_SCALES) == set(hm.NORM_SCALES) | {"final_norm"}


def test_the_ring_is_a_slot_s_and_is_sixteen_positions_whatever_the_length():
    params = make_params()
    short = make_engine(params, max_seq=32)
    long = make_engine(params, max_seq=4096)
    for eng in (short, long):
        rings = [leaf for name in ("k_win", "v_win") for leaf in eng.cache[name]]
        assert len(rings) == 2 * 6 and len(eng.cache["k_full"]) == 2
        assert all(leaf.shape == (SLOTS, W, 2 * 8) for leaf in rings)
    ring_bytes = SLOTS * 6 * 2 * W * 2 * 8 * 4
    assert kv_cache.slot_state_bytes(short.cache) == kv_cache.slot_state_bytes(
        long.cache) == ring_bytes
    assert short.page_bytes_each == 2 * PAGE * 2 * (8 + 8) * 4
    # what a decode step counts: one window layer's ring has room for the
    # window and holds min(pos + 1, window); the state is the rings, whole
    long.prefill(0, tokens_of(50, 3).tolist(), 4)
    long.prefill(2, tokens_of(5, 4).tolist(), 4)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[0], pos[0], tokens[2], pos[2] = 5, 50, 7, 5
    long.decode(tokens, pos)
    counted = long.step_counters
    assert counted["ring_positions_capacity_sum"] == 2 * W
    assert counted["window_positions_held_sum"] == W + 6
    assert counted["full_positions_held_sum"] == 51 + 6
    assert counted["slot_state_bytes_held_sum"] == 2 * ring_bytes // SLOTS
    assert counted["expert_pairs_total"] == 2 * 6 * 3


# -- the serving path against the reference's full forward -------------------------


def test_the_model_forward_matches_the_reference():
    params = make_params(3)
    toks = jnp.asarray(tokens_of(45, 5))
    got = hm.forward(params, toks, spec=SPEC)
    want = ref.forward(params, toks, ARCH)
    assert float(jnp.std(want)) > 0.5  # logits apart: a fault shows
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("length", [1, 3, 8, 13, 16, 17, 24, 30, 41])
def test_prefill_then_decode_matches_the_reference(length, kernel):
    """Contexts on both sides of a window of 16 that is two chunks long: the
    ring never wraps (1, 3), wraps during decode (8, 13), is filled exactly by
    the prompt, whose last chunk ends at the window (16), wraps by one row
    (17), by a whole chunk (24), is wrapped twice before the prompt ends (30
    pads its last chunk, 41 leaves one real row in it) and a third time in
    decode; then 10 decode steps. Logits, not tokens. The engine is the one
    the other lengths used, unscrubbed."""
    params, eng = shared(0, kernel)
    seq, got = serve_alone(eng, tokens_of(length, length), 10)
    np.testing.assert_allclose(got, reference_rows(params, seq, length),
                               atol=ATOL)


@pytest.mark.parametrize("length", [5, 11, 12, 20, 37])
def test_a_window_that_no_chunk_or_page_divides(length):
    """A window of 12 over chunks of 8 and pages of 4: a chunk's end never
    falls on the ring's."""
    params, eng = shared(0, "gather", 12)
    seq, got = serve_alone(eng, tokens_of(length, length), 8)
    want = reference_rows(params, seq, length, ARCH._replace(window=12))
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_sequences_batched_together_get_the_logits_they_get_alone():
    """Three sequences enter the engine at different times, one short of the
    window, one past it, one twice round it: in one decode step a window
    layer is a full layer for one lane and a capped one for the others."""
    params, eng = shared(1)
    prompts = {0: tokens_of(19, 2), 1: tokens_of(5, 3), 2: tokens_of(37, 4)}
    steps = 10
    alone = {s: serve_alone(eng, p, steps, slot=s) for s, p in prompts.items()}
    seqs, rows, tasks = {}, {s: [] for s in prompts}, {}
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)

    def advance_prefill(slot):
        tok = eng.prefill_step(tasks[slot])
        if tok is not None:
            del tasks[slot]
            seqs[slot] = list(prompts[slot]) + [tok]
            rows[slot].append(eng.last_prefill_logits)

    def decode_once():
        for s, seq in seqs.items():
            tokens[s], pos[s] = seq[-1], len(seq) - 1
        out = eng.decode(tokens, pos)
        for s, seq in seqs.items():
            if len(rows[s]) <= steps:
                rows[s].append(eng.last_logits[s])
                seq.append(int(out[s]))

    for slot in (0, 1, 2):  # each admitted while the earlier ones decode
        tasks[slot] = eng.prefill_begin(slot, list(prompts[slot]), steps + 1)
        while slot in tasks:
            advance_prefill(slot)
            if seqs:
                decode_once()
    while any(len(r) <= steps for r in rows.values()):
        decode_once()
    for s in prompts:
        eng.release(s)
        seq, want = alone[s]
        assert seqs[s][: len(seq)] == seq
        np.testing.assert_allclose(np.stack(rows[s]), want, atol=ATOL)
        np.testing.assert_allclose(
            want, reference_rows(params, seq, len(prompts[s])), atol=ATOL)


def test_the_scheduler_serves_mixed_lengths_like_each_alone():
    params, eng = shared(2)
    lengths = [4, 23, 9, 40, 16, 1]
    reqs = [Request(uid=f"r{i}", prompt=tokens_of(n, 10 + i).tolist(),
                    max_new_tokens=6 + i) for i, n in enumerate(lengths)]
    alone = {r.uid: serve_alone(eng, r.prompt, r.max_new_tokens - 1)[0]
             for r in reqs}
    eng.reset_stats()
    results, report = ContinuousBatchingScheduler(eng, eos_id=None).run(
        copy.deepcopy(reqs))
    by_uid = {r.uid: r for r in results}
    for r in reqs:
        assert by_uid[r.uid].finish_reason == "length"
        assert by_uid[r.uid].tokens == alone[r.uid][len(r.prompt):]
    # the step's counts rode back with its tokens: a ring has room for the
    # window a live lane and holds at most that
    assert report.ring_positions_capacity_sum % W == 0
    assert 0 < report.window_positions_held_sum < report.ring_positions_capacity_sum
    assert report.window_positions_held_sum < report.full_positions_held_sum
    assert report.expert_pairs_here == report.expert_pairs_total > 0
    assert report.slot_state_bytes_held_sum == (
        report.ring_positions_capacity_sum * 6 * 2 * 2 * 8 * 4)


def test_a_slot_shows_its_next_occupant_nothing_without_a_scrub():
    """A ring is masked by position: whatever a longer sequence left in it,
    the next occupant reads only what it wrote itself."""
    params = make_params(4)
    eng = make_engine(params)
    eng.prefill(1, tokens_of(45, 7).tolist(), 4)
    eng.release(1)
    assert all(np.asarray(leaf[1]).all(-1).all() for leaf in eng.cache["k_win"])
    fresh = make_engine(params)
    for e in (eng, fresh):
        e.prefill(1, tokens_of(6, 8).tolist(), 4)
    np.testing.assert_array_equal(eng.last_prefill_logits,
                                  fresh.last_prefill_logits)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[1], pos[1] = 9, 6
    for e in (eng, fresh):
        e.decode(tokens, pos)
    np.testing.assert_array_equal(eng.last_logits[1], fresh.last_logits[1])


def test_the_quarantine_scrub_zeroes_pages_and_the_slot_s_rings():
    params = make_params()
    eng = make_engine(params)
    eng.prefill(2, tokens_of(21, 3).tolist(), 4)
    eng.prefill(0, tokens_of(7, 4).tolist(), 4)
    eng.poison_slot(2, 9)
    assert np.isnan(np.asarray(eng.cache["k_full"][0])).any()
    eng.scrub_slot(2, 8)
    for leaves in eng.cache.values():
        for leaf in leaves:
            assert np.isfinite(np.asarray(leaf)).all()
    for name in ("k_win", "v_win"):
        for leaf in eng.cache[name]:
            assert not np.asarray(leaf[2]).any() and np.asarray(leaf[0]).any()


def test_a_key_behind_the_window_changes_nothing():
    """One window layer: position i reads tokens (i - 16, i] and no other,
    through chunked prefill into a ring two chunks long."""
    one = dataclasses.replace(SPEC, attn_kinds=(hm.WINDOW,),
                              ffn_kinds=(hm.EXPERTS,))
    params = make_params(12, one)
    eng = make_engine(params, one)
    toks = tokens_of(37, 13)
    i = 36
    behind, inside = toks.copy(), toks.copy()
    behind[i - W] = (behind[i - W] + 1) % 96 + 1
    inside[i - W + 1] = (inside[i - W + 1] + 1) % 96 + 1
    served = {name: serve_alone(eng, t, 0)[1][0]
              for name, t in (("base", toks), ("behind", behind),
                              ("inside", inside))}
    base = hm.forward(params, jnp.asarray(toks), spec=one)[i]
    np.testing.assert_allclose(served["base"], base, atol=ATOL)
    np.testing.assert_array_equal(served["behind"], served["base"])
    assert np.abs(served["inside"] - served["base"]).max() > 1e-3


# -- each mechanism, left out, moves the logits past the tolerance ---------------------


@pytest.mark.parametrize("field,value", [
    ("output_gate", False), ("post_norms", False), ("embed_scale", 1.0),
    ("shared_width", 0), ("rotate_full", True), ("qk_norm", False),
])
def test_a_mechanism_left_out_moves_the_logits(field, value):
    params = make_params(3)
    toks = jnp.asarray(tokens_of(45, 5))
    want = ref.forward(params, toks, ARCH)
    bare = dataclasses.replace(SPEC, **{field: value})
    got = hm.forward(params, toks, spec=bare)
    assert float(jnp.abs(got - want).max()) > MOVED


def _stack_at(params, spec, toks, positions):
    """The layers over one sequence with every attention layer causal over
    the sequence itself, at the positions given."""
    s = len(toks)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    x = hm._embed(params, jnp.asarray(toks), spec)
    x, _ = hm._stack(
        spec, params, x, positions,
        lambda layer: lambda q, k, v, sink: hm.attend(q, k, v, causal, sink))
    return hm._logits(params, x, spec)


def test_full_layers_have_no_position_signal(monkeypatch):
    """A stack of full layers alone: shifting every position leaves the
    logits as they were, and no angle table is built at all; with one window
    layer in it the same shift moves them."""
    full_only = dataclasses.replace(SPEC, attn_kinds=(hm.FULL,) * 3,
                                    ffn_kinds=(hm.DENSE, hm.EXPERTS, hm.EXPERTS))
    params = make_params(7, full_only)
    toks = tokens_of(14, 2)
    calls = []
    real_rotary = hm.rotary
    monkeypatch.setattr(hm, "rotary", lambda *a, **kw: (
        calls.append(1), real_rotary(*a, **kw))[1])
    here = _stack_at(params, full_only, toks, jnp.arange(14))
    there = _stack_at(params, full_only, toks, jnp.arange(14) + 1000)
    assert not calls
    np.testing.assert_array_equal(here, there)
    mixed = dataclasses.replace(full_only, attn_kinds=(hm.FULL, hm.WINDOW, hm.FULL))
    params = make_params(7, mixed)
    here = _stack_at(params, mixed, toks, jnp.arange(14))
    there = _stack_at(params, mixed, toks, jnp.arange(14) * 3)
    assert len(calls) == 4  # the one window layer's q and k, twice
    assert float(jnp.abs(here - there).max()) > 1e-3


def test_the_gate_multiplies_the_context_before_the_output_projection():
    p = make_params(5)["layers"][3]
    h = jax.random.normal(jax.random.key(2), (9, D), jnp.float32)
    causal = jnp.arange(9)[None, :] <= jnp.arange(9)[:, None]
    seen = {}

    def attention(q, k, v, sink):
        seen["ctx"] = hm.attend(q, k, v, causal, sink)
        return seen["ctx"]

    got = hm.attention_op(p, h, jnp.arange(9), spec=SPEC, kind=hm.FULL,
                          attention=attention)
    gate = jax.nn.sigmoid(h @ p["w_gate"])
    want = (seen["ctx"].reshape(9, -1) * gate) @ p["wo"]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got - seen["ctx"].reshape(9, -1) @ p["wo"]).max()) > MOVED


def test_selection_is_by_score_plus_bias_and_weights_by_score():
    p = make_params(6)["layers"][2]
    p["router_bias"] = p["router_bias"] * 40.0  # the bias decides who is chosen
    h32 = jax.random.normal(jax.random.key(3), (11, D), jnp.float32)
    chosen, w = hm.route(p, h32, spec=SPEC)
    s = np.asarray(jax.nn.sigmoid(h32 @ p["router"]))
    by_bias = np.argsort(-(s + np.asarray(p["router_bias"])), -1)[:, :3]
    by_score = np.argsort(-s, -1)[:, :3]
    assert (np.sort(chosen, -1) == np.sort(by_bias, -1)).all()
    assert (np.sort(by_bias, -1) != np.sort(by_score, -1)).any()
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        w, 2.826 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    ref_chosen, ref_w = ref.route(p, h32, ARCH)
    assert (np.sort(chosen, -1) == np.sort(ref_chosen, -1)).all()
    np.testing.assert_allclose(np.sort(w, -1), np.sort(ref_w, -1), rtol=1e-6)


# -- the expert layer: a branch every token takes beside the routed sum -----------------


def _uncut_layer(p, h32):
    """The reference's whole FFN of an expert layer: the routed sum over all
    eight experts and the shared expert, once."""
    return ref._routed(p, h32, h32, ARCH, False) + ref.shared_expert(p, h32)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The 8 routed experts dealt over 4 chips, 2 each, the shared expert
    whole on every chip: the chips' routed parts and the shared expert
    COUNTED ONCE equal the uncut reference layer (the router is computed alike
    on every chip and adds nothing of its own)."""
    p = make_params(5)["layers"][3]
    h32 = jax.random.normal(jax.random.key(9), (21, D), jnp.float32)
    want = _uncut_layer(p, h32)
    whole, counts = hm.expert_layer(p, h32, spec=SPEC)
    np.testing.assert_allclose(whole, want, atol=ATOL)
    assert int(counts[0]) == int(counts[1]) == 21 * 3
    everyones = hm.gated_ffn(p, h32, "shared_")
    np.testing.assert_allclose(everyones, ref.shared_expert(p, h32), atol=ATOL)
    assert float(jnp.abs(everyones).max()) > MOVED
    total = jnp.zeros_like(want)
    pairs_here = 0
    for chip in range(4):
        ids = [2 * chip, 2 * chip + 1]
        share = dataclasses.replace(SPEC, experts_held=tuple(ids))
        part = {**p, **{k: p[k][jnp.asarray(ids)] for k in ("wg", "wu", "wd")}}
        y, counts = hm.expert_layer(part, h32, spec=share)
        # what a chip computes is its routed part and the shared expert
        want_share = ref._routed(part, h32, h32, ARCH._replace(held=tuple(ids)),
                                 False) + ref.shared_expert(p, h32)
        np.testing.assert_allclose(y, want_share, atol=ATOL)
        assert float(jnp.abs(y - everyones).max()) > 1e-3  # a routed part each
        total = total + (y - everyones)
        pairs_here += int(counts[1])
        assert int(counts[0]) == 21 * 3
    assert pairs_here == 21 * 3  # every pair lands on exactly one share
    np.testing.assert_allclose(total + everyones, want, atol=ATOL)


def test_padding_rows_and_dead_lanes_reach_no_routed_expert():
    """A row that `live` masks out is routed nowhere and counted nowhere; what
    it reads is the shared expert's output alone, which every row given gets."""
    p = make_params(5)["layers"][4]
    h32 = jax.random.normal(jax.random.key(4), (13, D), jnp.float32)
    live = jnp.arange(13) % 3 != 1
    y, counts = hm.expert_layer(p, h32, spec=SPEC, live=live)
    everyones = hm.gated_ffn(p, h32, "shared_")
    np.testing.assert_allclose(y[~live], everyones[~live], atol=1e-6)
    assert float(jnp.abs(y[live] - everyones[live]).max()) > 1e-3
    assert int(counts[0]) == int(counts[1]) == int(live.sum()) * 3
    whole, _ = hm.expert_layer(p, h32, spec=SPEC)
    np.testing.assert_allclose(y[live], whole[live], atol=1e-6)
    # the shared expert takes part in no count
    _, bare = hm.expert_layer(p, h32, spec=dataclasses.replace(
        SPEC, shared_width=0), live=live)
    np.testing.assert_array_equal(counts, bare)


# -- what the engine refuses for this model --------------------------------------------


def test_each_refusal_raises_by_name_with_the_ring_s_reason():
    from distributeddeeplearning_tpu.spec import SpeculativeDecoder

    params = make_params()
    model = hybrid_model(SPEC)
    assert model.refuses == frozenset(FEATURES) == frozenset(model.reasons)
    kw = dict(model=model, batch_slots=2, max_seq=32, page_size=PAGE,
              num_pages=16, prefill_chunk=CHUNK)
    with pytest.raises(Refused, match="prefix_cache.*window layers' last positions"):
        PagedInferenceEngine(params, **kw)  # the engine's default is on
    with pytest.raises(Refused, match="int8_pool.*per-slot"):
        PagedInferenceEngine(params, prefix_cache=False, cache_dtype=jnp.int8,
                             **kw)
    engine = PagedInferenceEngine(params, prefix_cache=False, **kw)
    with pytest.raises(Refused, match="verify.*window layers' last positions"):
        SpeculativeDecoder(engine)
