"""Gated short-convolution layers beside attention layers, with sparse
experts, on the paged serving path: the third layer kind of
``models/hybrid_moe_transformer.py``, its per-slot state in
``serve/kv_cache.init_hybrid_cache`` and the engine's description of it
(``serve/served_model.hybrid_model``), all against the plain reference the
benchmark keeps (``benchmarks/families/lfm2_moe_reference.py``, which imports
nothing of the program).

The tiny size has every mechanism present: 2 dense convolution layers, then
``attention, conv, conv, conv`` twice; 3 taps; 4 query / 2 KV heads of 8 with
a learned norm a head and rotary over the whole head; 8 experts, 3 a token,
with a correction bias that changes who is chosen; the head tied to the
embedding; no window layer at all (window 0).

Tolerance: everything runs in float32 on the CPU, where the program and the
reference differ only in the order of float32 sums: ``ATOL`` 1e-4 on logits
of spread 1.7 (the widest read over the sixteen served prompts below is
3.3e-5, through ten layers).  Every mechanism left out moves the logits by
1e-2 or more (the tests below that leave one out read so).
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import importlib
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

FAMILIES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "families",
)
if FAMILIES not in sys.path:
    sys.path.insert(0, FAMILIES)
ref = importlib.import_module("lfm2_moe_reference")

TINY = {
    "model_type": "lfm2_moe", "vocab_size": 97, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "rope_theta": 1000000,
    "num_hidden_layers": 10,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 2,
    "num_dense_layers": 2, "intermediate_size": 64,
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1,
}
SPEC = hm.spec_from_config(TINY)
ARCH = ref.arch_of(TINY)
ATOL = 1e-4
MOVED = 1e-2  # what leaving a mechanism out moves the logits by, at least
PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
D = TINY["hidden_size"]


def make_params(seed=0, spec=SPEC, std=0.3):
    params = hm.init_params(jax.random.key(seed), spec, std=std)
    keys = iter(jax.random.split(jax.random.key(seed + 1000), 64))
    for p in params["layers"]:
        if "router_bias" in p:  # a correction bias that changes who is chosen
            p["router_bias"] = p["router_bias"] * 0.5
        for name in ("q_norm", "k_norm"):  # norm scales that are not all 1
            if name in p:
                p[name] = 1.0 + 0.3 * jax.random.normal(next(keys), p[name].shape)
    return params


def make_engine(params, spec=SPEC, *, kernel="gather", slots=SLOTS,
                max_seq=MAX_SEQ, pages=48, **kw):
    return PagedInferenceEngine(
        params, model=hybrid_model(spec), batch_slots=slots, max_seq=max_seq,
        page_size=PAGE, num_pages=pages, prefill_chunk=CHUNK,
        prefix_cache=False, capture_logits=True, decode_kernel=kernel, **kw)


@functools.lru_cache(maxsize=None)
def shared(seed=0, kernel="gather"):
    """One engine a (weights, kernel), used by one test after another with
    NO scrub between them: a slot's state is whatever its last user left."""
    params = make_params(seed)
    return params, make_engine(params, kernel=kernel)


def tokens_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def serve_alone(eng, prompt, steps, *, slot=1, between_chunks=None):
    """Prefill `prompt` in chunks, then `steps` greedy decode steps, in
    `slot`, which is released afterwards: (the sequence, the logits of every
    position from the prompt's last on)."""
    task = eng.prefill_begin(slot, list(prompt), steps + 1)
    tok = None
    while tok is None:
        tok = eng.prefill_step(task)
        if tok is None and between_chunks is not None:
            between_chunks(eng)
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


def reference_rows(params, seq, length):
    want = ref.forward(params, jnp.asarray(seq[:-1]), ARCH)
    return np.asarray(want)[length - 1:]


# -- the spec and the cache ---------------------------------------------------------


def test_the_spec_reads_the_published_keys():
    assert SPEC.attn_kinds == (hm.CONV, hm.CONV) + (
        hm.FULL, hm.CONV, hm.CONV, hm.CONV) * 2
    assert SPEC.ffn_kinds == (hm.DENSE,) * 2 + (hm.EXPERTS,) * 8
    assert (SPEC.k_dim, SPEC.v_dim, SPEC.rotary_dim) == (8, 8, 8)
    assert SPEC.window == 0 and not SPEC.layers_of(hm.WINDOW)
    assert SPEC.conv_taps == 3 and SPEC.qk_norm and SPEC.tied_head
    assert SPEC.topk_eps == 1e-6 and SPEC.experts_held == tuple(range(8))
    # a file keeps the published pattern whole and says which layers it runs
    cut = hm.spec_from_config(dict(TINY, num_hidden_layers=4,
                                   layers_kept=[0, 6, 7, 8]))
    assert cut.attn_kinds == (hm.CONV, hm.FULL, hm.CONV, hm.CONV)
    assert cut.ffn_kinds == (hm.DENSE, hm.EXPERTS, hm.EXPERTS, hm.EXPERTS)
    with pytest.raises(ValueError, match="layers_kept"):
        hm.spec_from_config(dict(TINY, layers_kept=[0, 1]))
    with pytest.raises(ValueError, match="conv_bias"):
        hm.spec_from_config(dict(TINY, conv_bias=True))
    with pytest.raises(ValueError, match="conv_taps"):
        dataclasses.replace(SPEC, conv_taps=1)
    with pytest.raises(ValueError, match="window"):
        dataclasses.replace(SPEC, attn_kinds=(hm.WINDOW,) * 10)


def test_the_state_is_a_slot_s_and_does_not_grow_with_the_sequence():
    params = make_params()
    short = make_engine(params, max_seq=32)
    long = make_engine(params, max_seq=4096)
    for eng in (short, long):
        assert not eng.cache["k_win"] and not eng.cache["v_win"]
        states = eng.cache["conv_state"]
        assert len(states) == 8 and len(eng.cache["k_full"]) == 2
        assert all(leaf.shape == (SLOTS, 2 * D) for leaf in states)
    state_bytes = SLOTS * 8 * 2 * D * 4
    assert kv_cache.slot_state_bytes(short.cache) == kv_cache.slot_state_bytes(
        long.cache) == state_bytes
    # pages are the attention layers' alone, and admission counts pages alone
    assert short.page_bytes_each == 2 * PAGE * 2 * (8 + 8) * 4
    assert kv_cache.cache_bytes(short.cache) == (
        49 * short.page_bytes_each + state_bytes)
    assert len(kv_cache.paged_leaves(short.cache)) == 4
    assert long.required_pages(1000, 24) == 256
    # what a decode step counts: a live lane holds its state whole and the
    # K/V of every position it has written
    long.prefill(0, tokens_of(50, 3).tolist(), 4)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[0], pos[0] = 5, 50
    long.decode(tokens, pos)
    counted = long.step_counters
    assert counted["slot_state_bytes_held_sum"] == 8 * 2 * D * 4
    assert counted["kv_bytes_held_sum"] == 51 * 2 * 2 * (8 + 8) * 4
    assert counted["full_positions_held_sum"] == 51
    assert counted["window_positions_held_sum"] == 0
    assert counted["expert_pairs_here"] == counted["expert_pairs_total"] == 8 * 3


# -- the serving path against the reference's full forward -------------------------


def test_the_model_forward_matches_the_reference():
    params = make_params(3)
    toks = jnp.asarray(tokens_of(37, 5))
    got = hm.forward(params, toks, spec=SPEC)
    want = ref.forward(params, toks, ARCH)
    assert float(jnp.std(want)) > 0.5  # logits apart: a fault shows
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("length", [1, 2, 3, 8, 9, 13, 21, 30])
def test_prefill_then_decode_matches_the_reference(length, kernel):
    """Prompts shorter than the state (1, 2), that pad a chunk (3, 13, 21,
    30), end on a chunk and page edge (8), leave one real row in their last
    chunk (9) and span several chunks and pages; then 10 decode steps, which
    cross page edges. Logits, not tokens. The engine is the one the other
    lengths used, unscrubbed."""
    params, eng = shared(0, kernel)
    seq, got = serve_alone(eng, tokens_of(length, length), 10)
    np.testing.assert_allclose(got, reference_rows(params, seq, length),
                               atol=ATOL)


def test_sequences_batched_together_get_the_logits_they_get_alone():
    """Three sequences of mixed length enter the engine at different times:
    one decodes while the next prefills chunk by chunk in another slot, and
    neither's state moves the other's."""
    params, eng = shared(1)
    prompts = {0: tokens_of(19, 2), 1: tokens_of(5, 3), 2: tokens_of(27, 4)}
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
    lengths = [4, 23, 9, 30, 14, 1]
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
    # the step's counts rode back with its tokens: every expert is held, and
    # state and K/V are counted in bytes
    assert report.expert_pairs_here == report.expert_pairs_total > 0
    assert report.experts_touched_sum > 0
    assert report.slot_state_bytes_held_sum > 0
    assert report.kv_bytes_held_sum == (
        report.full_positions_held_sum * 2 * 2 * (8 + 8) * 4)
    assert report.slot_state_bytes_held_sum % (8 * 2 * D * 4) == 0


def test_a_slot_shows_its_next_occupant_nothing_without_a_scrub():
    """A state has no positions to mask by: the next occupant's first chunk
    starts from zeros whatever the slot holds, and no scrub runs between
    them (release calls none)."""
    params = make_params(4)
    eng = make_engine(params)
    eng.prefill(1, tokens_of(29, 7).tolist(), 4)
    assert all(np.asarray(leaf[1]).any() for leaf in eng.cache["conv_state"])
    eng.release(1)
    assert all(np.asarray(leaf[1]).any() for leaf in eng.cache["conv_state"])
    eng.prefill(1, tokens_of(6, 8).tolist(), 4)
    fresh = make_engine(params)
    fresh.prefill(1, tokens_of(6, 8).tolist(), 4)
    np.testing.assert_array_equal(eng.last_prefill_logits,
                                  fresh.last_prefill_logits)
    for a, b in zip(eng.cache["conv_state"], fresh.cache["conv_state"]):
        np.testing.assert_array_equal(a[1], b[1])


def test_the_quarantine_scrub_zeroes_pages_and_state():
    params = make_params()
    eng = make_engine(params)
    eng.prefill(2, tokens_of(11, 3).tolist(), 4)
    eng.prefill(0, tokens_of(7, 4).tolist(), 4)
    eng.poison_slot(2, 9)
    assert np.isnan(np.asarray(eng.cache["k_full"][0])).any()
    eng.scrub_slot(2, 8)
    for leaves in eng.cache.values():
        for leaf in leaves:
            assert np.isfinite(np.asarray(leaf)).all()
    for leaf in eng.cache["conv_state"]:
        assert not np.asarray(leaf[2]).any() and np.asarray(leaf[0]).any()
    pages = eng._slot_pages[2]
    kept = np.asarray(eng.cache["k_full"][0][np.asarray(pages[:2])])
    assert kept.any() and not np.asarray(
        eng.cache["k_full"][0][np.asarray(pages[2:])]).any()


# -- each mechanism, left out, moves the logits past the tolerance ---------------------


def _zero_state(eng):
    eng._cache = dict(eng.cache, conv_state=tuple(
        jnp.zeros_like(leaf) for leaf in eng.cache["conv_state"]))


@pytest.mark.parametrize("where", ["between_chunks", "before_decode"])
def test_the_state_carried_across_an_edge_left_out_moves_the_logits(where):
    params, eng = shared(0)
    prompt = tokens_of(21, 21)
    if where == "between_chunks":
        seq, got = serve_alone(eng, prompt, 0, between_chunks=_zero_state)
        want = reference_rows(params, seq, len(prompt))
    else:
        tok = eng.prefill(1, prompt.tolist(), 4)
        _zero_state(eng)
        tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tokens[1], pos[1] = tok, len(prompt)
        eng.decode(tokens, pos)
        eng.release(1)
        got = eng.last_logits[1][None]
        want = np.asarray(ref.forward(
            params, jnp.asarray(list(prompt) + [tok]), ARCH))[-1:]
    assert np.abs(got - want).max() > MOVED


def test_the_qk_norm_left_out_moves_the_logits():
    params = make_params(3)
    toks = jnp.asarray(tokens_of(37, 5))
    want = ref.forward(params, toks, ARCH)
    bare = dataclasses.replace(SPEC, qk_norm=False)
    assert float(jnp.abs(hm.forward(params, toks, spec=bare) - want).max()) > MOVED


def test_selecting_without_the_bias_moves_the_logits():
    params = make_params(3)
    toks = jnp.asarray(tokens_of(37, 5))
    want = ref.forward(params, toks, ARCH)
    unbiased = copy.copy(params)
    unbiased["layers"] = [
        {**p, "router_bias": jnp.zeros_like(p["router_bias"])}
        if "router_bias" in p else p for p in params["layers"]]
    got = hm.forward(unbiased, toks, spec=SPEC)
    assert float(jnp.abs(got - want).max()) > MOVED


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
        w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    ref_chosen, ref_w = ref.route(p, h32, ARCH)
    assert (np.sort(chosen, -1) == np.sort(ref_chosen, -1)).all()
    np.testing.assert_allclose(np.sort(w, -1), np.sort(ref_w, -1), rtol=1e-6)
    # the sum's epsilon is added before the division, and scales nothing else
    _, w_half = hm.route(p, h32, spec=dataclasses.replace(SPEC, topk_eps=0.5))
    np.testing.assert_allclose(
        w_half, picked / (picked.sum(-1, keepdims=True) + 0.5), rtol=1e-6)


# -- the convolution operator ----------------------------------------------------------


def test_the_conv_operator_is_the_three_term_causal_sum():
    """Against the sum written out in numpy: position t reads inputs t-2,
    t-1 and t and no other, and positions before the start read 0."""
    p = make_params(5)["layers"][0]
    h = jax.random.normal(jax.random.key(1), (9, D), jnp.float32)
    whole = lambda u: tuple(jnp.pad(u, ((b, 0), (0, 0)))[:9] for b in (2, 1, 0))
    got = np.asarray(hm.short_conv(p, h, spec=SPEC, state=whole))
    bcx = np.asarray(h) @ np.asarray(p["w_in"])
    u = bcx[:, :D] * bcx[:, 2 * D:]
    w = np.asarray(p["conv_w"])
    c = np.zeros_like(u)
    for t in range(9):
        for j in range(3):
            if t - 2 + j >= 0:
                c[t] += w[j] * u[t - 2 + j]
    want = (bcx[:, D:2 * D] * c) @ np.asarray(p["w_out"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        got, ref._conv_op(p, h, ARCH, False), atol=1e-5)
    later = h.at[5].add(1.0)  # an input moves its own row and the two after
    moved = np.abs(np.asarray(
        hm.short_conv(p, later, spec=SPEC, state=whole)) - got).max(-1)
    assert (moved[:5] == 0).all() and (moved[5:8] > 1e-4).all()
    assert (moved[8:] == 0).all()


def test_a_model_with_no_window_layer_computes_no_ring_position(monkeypatch):
    def no_ring(*a, **k):
        raise AssertionError("ring_positions computed for a window of 0")

    monkeypatch.setattr(hm, "ring_positions", no_ring)
    params = make_params()
    seq, got = serve_alone(make_engine(params), tokens_of(11, 2), 2)
    np.testing.assert_allclose(got, reference_rows(params, seq, 11), atol=ATOL)


# -- the head ---------------------------------------------------------------------------------


def test_the_head_reads_the_embedding_s_own_buffer():
    params = make_params()
    assert "head" not in params and "head" not in hm.init_params(
        jax.random.key(0), SPEC)
    x = jax.random.normal(jax.random.key(2), (5, D), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: hm._logits(p, x, SPEC))(params, x)
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1 and not any(
        e.primitive.name in ("transpose", "copy") for e in jaxpr.eqns)
    embed_var = jaxpr.jaxpr.invars[
        jax.tree_util.tree_leaves(params).index(params["embed"])]
    assert dots[0].invars[1] is embed_var  # the argument itself, untransposed
    assert dots[0].params["dimension_numbers"] == (((1,), (1,)), ((), ()))
    want = hm.rms_norm(x, params["final_norm"], SPEC.eps) @ params["embed"].T
    np.testing.assert_allclose(hm._logits(params, x, SPEC), want, atol=1e-5)
    # and the engine holds the embedding once
    eng = make_engine(params)
    assert eng.weights_bytes == sum(
        leaf.size * 4 for leaf in jax.tree_util.tree_leaves(params))


# -- the expert layer ---------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """The 8 experts dealt over 4 chips, 2 each: over all the shares the
    layer's output equals the uncut reference's (the router is computed
    alike on every chip and adds nothing of its own)."""
    p = make_params(5)["layers"][3]
    h32 = jax.random.normal(jax.random.key(9), (21, D), jnp.float32)
    want = ref._experts(p, h32, h32, ARCH, False)
    whole, counts = hm.expert_layer(p, h32, spec=SPEC)
    np.testing.assert_allclose(whole, want, atol=ATOL)
    assert int(counts[0]) == int(counts[1]) == 21 * 3
    total = jnp.zeros_like(want)
    pairs_here = 0
    for chip in range(4):
        ids = [2 * chip, 2 * chip + 1]
        share = dataclasses.replace(SPEC, experts_held=tuple(ids))
        part = {**p, **{k: p[k][jnp.asarray(ids)] for k in ("wg", "wu", "wd")}}
        y, counts = hm.expert_layer(part, h32, spec=share)
        assert float(jnp.abs(y).max()) > 1e-3  # every share gives a part
        total = total + y
        pairs_here += int(counts[1])
        assert int(counts[0]) == 21 * 3
        want_share = ref._experts(part, h32, h32, ARCH._replace(held=tuple(ids)),
                                  False)
        np.testing.assert_allclose(y, want_share, atol=ATOL)
    assert pairs_here == 21 * 3  # every pair lands on exactly one share
    np.testing.assert_allclose(total, want, atol=ATOL)


# -- what the engine refuses for this model --------------------------------------------


def test_each_refusal_raises_by_name_with_the_state_s_reason():
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.spec import SpeculativeDecoder

    params = make_params()
    model = hybrid_model(SPEC)
    assert model.refuses == frozenset(FEATURES) == frozenset(model.reasons)
    kw = dict(model=model, batch_slots=2, max_seq=32, page_size=PAGE,
              num_pages=16, prefill_chunk=CHUNK)
    with pytest.raises(Refused, match="prefix_cache.*convolution layers' state"):
        PagedInferenceEngine(params, **kw)  # the engine's default is on
    with pytest.raises(Refused, match="int8_pool.*per-slot"):
        PagedInferenceEngine(params, prefix_cache=False, cache_dtype=jnp.int8,
                             **kw)
    with pytest.raises(Refused, match="host_tier.*slot's state"):
        PagedInferenceEngine(params, prefix_cache=False, host_pages=4, **kw)
    mesh = create_mesh(MeshSpec(tensor=2), devices=jax.devices()[:2])
    with pytest.raises(Refused, match="tensor_mesh.*per-slot"):
        PagedInferenceEngine(params, prefix_cache=False, mesh=mesh, **kw)
    engine = PagedInferenceEngine(params, prefix_cache=False, **kw)
    with pytest.raises(Refused, match="verify.*convolution layers' state"):
        SpeculativeDecoder(engine)
