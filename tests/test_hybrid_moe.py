"""Window and full attention layers in one stack, with sparse experts, on the
paged serving path: the model (``models/hybrid_moe_transformer.py``), its
two-kind cache (``serve/kv_cache.init_hybrid_cache``), the engine's model
description (``serve/served_model.py``) and the grouped-query decode kernel,
all against the plain reference that the benchmark keeps
(``benchmarks/families/mimo_v2_reference.py``, which imports nothing of the
program).

The tiny size has every mechanism present: 2 full + 3 window layers, window
8, 2 and 4 KV heads, key width 12 != value width 8 with 4 of 12 dims rotated,
a dense first FFN and then 16 experts of which 4 are held, 3 a token.
Tolerances: everything runs in float32 on the CPU, where the program and the
reference differ only in the order of float32 sums (<= 2e-5 on logits of
spread ~0.5); every mechanism left out moves the logits by 1e-2 or more (the
tests below that leave one out read so).
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
from distributeddeeplearning_tpu.models import pipelined_transformer as pt
from distributeddeeplearning_tpu.ops import flash_decode as fd
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
ref = importlib.import_module("mimo_v2_reference")

TINY = {
    "vocab_size": 97, "hidden_size": 32, "num_attention_heads": 8,
    "head_dim": 12, "v_head_dim": 8, "partial_rotary_factor": 0.334,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "sliding_window": 8, "rope_theta": 5e6, "swa_rope_theta": 1e4,
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True,
    "attention_value_scale": 0.707, "layernorm_epsilon": 1e-5,
    "num_hidden_layers": 5, "hybrid_layer_pattern": [0, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1], "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "experts_held": [0, 1, 2, 3],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": None,
}
SPEC = hm.spec_from_config(TINY)
ARCH = ref.arch_of(TINY)
ATOL = 2e-5
PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64


def make_params(seed=0, spec=SPEC, std=0.3):
    params = hm.init_params(jax.random.key(seed), spec, std=std)
    for p in params["layers"]:  # sinks and correction biases that matter
        if "sink" in p:
            p["sink"] = p["sink"] * 5.0
        if "router_bias" in p:
            p["router_bias"] = p["router_bias"] * 0.5
    return params


def make_engine(params, spec=SPEC, *, kernel="gather", slots=SLOTS,
                max_seq=MAX_SEQ, pages=48, **kw):
    return PagedInferenceEngine(
        params, model=hybrid_model(spec), batch_slots=slots, max_seq=max_seq,
        page_size=PAGE, num_pages=pages, prefill_chunk=CHUNK,
        prefix_cache=False, capture_logits=True, decode_kernel=kernel, **kw)


def tokens_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def serve_alone(params, prompt, steps, *, kernel="gather", spec=SPEC):
    """Prefill `prompt` in chunks, then `steps` greedy decode steps, in slot
    1 of a fresh engine: (the sequence, the logits of every position from the
    prompt's last on)."""
    eng = make_engine(params, spec, kernel=kernel)
    tok = eng.prefill(1, list(prompt), steps + 1)
    seq, rows = list(prompt) + [tok], [eng.last_prefill_logits]
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for _ in range(steps):
        tokens[1], pos[1] = seq[-1], len(seq) - 1
        out = eng.decode(tokens, pos)
        rows.append(eng.last_logits[1])
        seq.append(int(out[1]))
    return seq, np.stack(rows)


# -- the serving path against the reference's full forward -------------------------


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("length", [3, 8, 13, 21, 30])
def test_prefill_then_decode_matches_the_reference(length, kernel):
    """Prompts that stay inside the window (3), end on a chunk and page edge
    (8), cross the window off every edge (13, 21) and span several chunks and
    pages (30); then 12 decode steps, which carry 3 and 8 across the window
    too. Logits, not tokens."""
    params = make_params()
    seq, got = serve_alone(params, tokens_of(length), 12, kernel=kernel)
    want = ref.forward(params, jnp.asarray(seq[:-1]), ARCH)
    np.testing.assert_allclose(got, np.asarray(want)[length - 1:], atol=ATOL)


def test_the_model_forward_matches_the_reference():
    params = make_params(3)
    toks = jnp.asarray(tokens_of(37, 5))
    np.testing.assert_allclose(
        hm.forward(params, toks, spec=SPEC), ref.forward(params, toks, ARCH),
        atol=ATOL)


def test_sequences_batched_together_get_the_logits_they_get_alone():
    """Three sequences of mixed length enter the engine at different times:
    one decodes while the next prefills chunk by chunk in another slot."""
    params = make_params(1)
    prompts = {0: tokens_of(19, 2), 1: tokens_of(5, 3), 2: tokens_of(27, 4)}
    steps = 10
    alone = {s: serve_alone(params, p, steps) for s, p in prompts.items()}
    eng = make_engine(params)
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
        seq, want = alone[s]
        assert seqs[s][: len(seq)] == seq
        np.testing.assert_allclose(np.stack(rows[s]), want, atol=ATOL)


def test_the_scheduler_serves_mixed_lengths_like_each_alone():
    params = make_params(2)
    lengths = [4, 23, 9, 30, 14, 6]
    reqs = [Request(uid=f"r{i}", prompt=tokens_of(n, 10 + i).tolist(),
                    max_new_tokens=6 + i) for i, n in enumerate(lengths)]
    eng = make_engine(params)
    results, report = ContinuousBatchingScheduler(eng, eos_id=None).run(
        copy.deepcopy(reqs))
    by_uid = {r.uid: r for r in results}
    for r in reqs:
        seq, _ = serve_alone(params, r.prompt, r.max_new_tokens - 1)
        assert by_uid[r.uid].finish_reason == "length"
        assert by_uid[r.uid].tokens == seq[len(r.prompt):]
    # the step's counts rode back with its tokens
    assert report.expert_pairs_total > 0
    assert 0 < report.expert_pairs_here < report.expert_pairs_total
    assert report.full_positions_held_sum > report.window_positions_held_sum > 0
    assert report.experts_touched_sum > 0
    assert report.expert_tokens_max_sum >= report.expert_tokens_mean_sum > 0


def test_a_slot_shows_its_next_occupant_nothing():
    params = make_params(4)
    eng = make_engine(params)
    eng.prefill(1, tokens_of(29, 7).tolist(), 4)
    eng.release(1)
    eng.prefill(1, tokens_of(6, 8).tolist(), 4)
    fresh = make_engine(params)
    fresh.prefill(1, tokens_of(6, 8).tolist(), 4)
    np.testing.assert_array_equal(eng.last_prefill_logits,
                                  fresh.last_prefill_logits)


# -- the expert layer ------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """Over all the shares of the experts the layer's output equals the
    uncut reference's (the router is computed alike on every chip and adds
    nothing of its own, so nothing is counted twice)."""
    whole = dataclasses.replace(SPEC, experts_held=tuple(range(16)))
    p = make_params(5, whole)["layers"][1]
    h32 = jax.random.normal(jax.random.key(9), (21, 32), jnp.float32)
    arch_whole = ARCH._replace(held=tuple(range(16)))
    want = ref._experts(p, h32, h32, arch_whole, False)
    total = jnp.zeros_like(want)
    pairs_here = 0
    for chip in range(4):  # chip i holds experts [4i, 4i + 4)
        ids = list(range(4 * chip, 4 * chip + 4))
        share = dataclasses.replace(whole, experts_held=tuple(ids))
        part = {**p, **{k: p[k][jnp.asarray(ids)] for k in ("wg", "wu", "wd")}}
        y, counts = hm.expert_layer(part, h32, spec=share)
        assert float(jnp.abs(y).max()) > 1e-3  # every share gives a part
        total = total + y
        pairs_here += int(counts[1])
        assert int(counts[0]) == 21 * 3
    assert pairs_here == 21 * 3  # every pair lands on exactly one share
    np.testing.assert_allclose(total, want, atol=ATOL)


def test_selection_is_by_score_plus_bias_and_weights_by_score():
    p = make_params(6)["layers"][2]
    p["router_bias"] = p["router_bias"] * 40.0  # the bias decides who is chosen
    h32 = jax.random.normal(jax.random.key(3), (11, 32), jnp.float32)
    chosen, w = hm.route(p, h32, spec=SPEC)
    s = np.asarray(jax.nn.sigmoid(h32 @ p["router"]))
    by_bias = np.argsort(-(s + np.asarray(p["router_bias"])), -1)[:, :3]
    by_score = np.argsort(-s, -1)[:, :3]
    assert (np.sort(chosen, -1) == np.sort(by_bias, -1)).all()
    assert (np.sort(by_bias, -1) != np.sort(by_score, -1)).any()
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    ref_chosen, ref_w = ref.route(p, h32, ARCH)
    assert (np.sort(chosen, -1) == np.sort(ref_chosen, -1)).all()
    np.testing.assert_allclose(np.sort(w, -1), np.sort(ref_w, -1), rtol=1e-5)


def test_absent_and_dead_pairs_reach_no_expert():
    p = make_params(7)["layers"][1]
    h32 = jax.random.normal(jax.random.key(4), (9, 32), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, True, True, True, False])
    y, counts = hm.expert_layer(p, h32, spec=SPEC, live=live)
    assert int(counts[0]) == 6 * 3
    assert not np.asarray(y)[~np.asarray(live)].any()
    alone, _ = hm.expert_layer(p, h32[live], spec=SPEC)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(live)], alone, atol=1e-6)
    nobody = dataclasses.replace(SPEC, experts_held=(12, 13, 14, 15))
    chosen, _ = hm.route(p, h32, spec=SPEC)
    y, counts = hm.expert_layer(p, h32, spec=nobody)
    hit = np.isin(np.asarray(chosen), [12, 13, 14, 15])
    assert int(counts[1]) == hit.sum()
    assert not np.asarray(y)[~hit.any(-1)].any()


@pytest.mark.parametrize("crowded", [False, True])
def test_the_grouped_products_follow_the_load_and_drop_nothing(crowded):
    """2 of 16 experts held: the products run over the first half of the
    sorted pairs when the pairs here fit there, and over all of them when a
    crowd picks the held experts; either way every pair here is computed."""
    two = dataclasses.replace(SPEC, experts_held=(5, 9))
    p = make_params(13, two)["layers"][1]
    if crowded:  # everybody chooses the two held experts
        p["router_bias"] = p["router_bias"].at[jnp.asarray([5, 9])].set(100.0)
    h32 = jax.random.normal(jax.random.key(8), (64, 32), jnp.float32)
    y, counts = hm.expert_layer(p, h32, spec=two)
    few = 4 * 64 * 3 * 2 // 16
    assert (int(counts[1]) > few) == crowded
    if crowded:
        assert int(counts[1]) == 2 * 64
    want = ref._experts(p, h32, h32, ARCH._replace(held=(5, 9)), False)
    np.testing.assert_allclose(y, want, atol=ATOL)


# -- attention: sink, rotary, window -----------------------------------------------------


def test_a_very_negative_sink_is_no_sink_and_a_real_one_is_not():
    params = make_params(8)
    toks = jnp.asarray(tokens_of(20, 9))
    base = hm.forward(params, toks, spec=SPEC)
    gone = jax.tree_util.tree_map(lambda a: a, params)
    for p in gone["layers"]:
        if "sink" in p:
            p["sink"] = jnp.full_like(p["sink"], -1e9)
    bare = dataclasses.replace(SPEC, sink_window=False)
    np.testing.assert_allclose(hm.forward(gone, toks, spec=SPEC),
                               hm.forward(params, toks, spec=bare), atol=1e-6)
    assert float(jnp.abs(base - hm.forward(gone, toks, spec=SPEC)).max()) > 1e-2


def test_rotary_turns_the_leading_part_by_the_kind_s_theta():
    x = jax.random.normal(jax.random.key(2), (6, 3, 12), jnp.float32)
    pos = jnp.arange(6) * 1000
    for theta in (5e6, 1e4):
        out = hm.rotary(x, pos, rotary_dim=4, theta=theta)
        np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
        np.testing.assert_allclose(out[0], x[0], atol=1e-6)  # position 0
        np.testing.assert_allclose(
            jnp.linalg.norm(out[..., :4], axis=-1),
            jnp.linalg.norm(x[..., :4], axis=-1), rtol=1e-5)
        shifted = ref._rotary(jnp.concatenate(
            [jnp.zeros((1000, 3, 12)), x[1:2]]), 4, theta)[1000]
        np.testing.assert_allclose(out[1], shifted, atol=1e-5)
    a = hm.rotary(x, pos, rotary_dim=4, theta=5e6)
    b = hm.rotary(x, pos, rotary_dim=4, theta=1e4)
    assert float(jnp.abs(a - b).max()) > 1e-2
    # the two kinds of layer really use different thetas
    params = make_params(10)
    toks = jnp.asarray(tokens_of(25, 11))
    same = dataclasses.replace(SPEC, theta_window=SPEC.theta_full)
    assert float(jnp.abs(hm.forward(params, toks, spec=SPEC)
                         - hm.forward(params, toks, spec=same)).max()) > 1e-3


ONE_WINDOW_LAYER = dataclasses.replace(SPEC, attn_kinds=(1,), ffn_kinds=(1,))


def test_a_key_behind_the_window_changes_nothing():
    """One window layer: position i reads tokens (i - 8, i] and no other,
    through the model's forward and through chunked prefill and the ring."""
    params = make_params(12, ONE_WINDOW_LAYER)
    toks = tokens_of(30, 13)
    i = 29
    behind, inside = toks.copy(), toks.copy()
    behind[i - 8] = (behind[i - 8] + 1) % 96 + 1
    inside[i - 7] = (inside[i - 7] + 1) % 96 + 1
    base = hm.forward(params, jnp.asarray(toks), spec=ONE_WINDOW_LAYER)[i]
    np.testing.assert_array_equal(
        hm.forward(params, jnp.asarray(behind), spec=ONE_WINDOW_LAYER)[i], base)
    moved = hm.forward(params, jnp.asarray(inside), spec=ONE_WINDOW_LAYER)[i]
    assert float(jnp.abs(moved - base).max()) > 1e-3
    served = {}
    for name, t in (("base", toks), ("behind", behind), ("inside", inside)):
        _, rows = serve_alone(params, t, 0, spec=ONE_WINDOW_LAYER)
        served[name] = rows[0]
    np.testing.assert_allclose(served["base"], base, atol=ATOL)
    np.testing.assert_array_equal(served["behind"], served["base"])
    assert np.abs(served["inside"] - served["base"]).max() > 1e-3


def test_the_window_cache_does_not_grow_with_the_sequence():
    params = make_params()
    short = make_engine(params, max_seq=32, pages=48)
    long = make_engine(params, max_seq=4096, pages=48)
    for eng in (short, long):
        rings = [leaf for name in kv_cache.RING_LEAVES
                 for leaf in eng.cache[name]]
        assert len(rings) == 2 * 3
        assert all(leaf.shape[:2] == (SLOTS, SPEC.window) for leaf in rings)
    assert kv_cache.slot_state_bytes(short.cache) == kv_cache.slot_state_bytes(
        long.cache) == SLOTS * 8 * 3 * 4 * (12 + 8) * 4
    assert short.kv_bytes() == long.kv_bytes()
    # pages are the full layers' alone, and admission counts pages alone
    assert short.page_bytes_each == 2 * PAGE * 2 * (12 + 8) * 4
    assert kv_cache.cache_bytes(short.cache) == (
        49 * short.page_bytes_each + kv_cache.slot_state_bytes(short.cache))
    assert long.required_pages(1000, 24) == 256
    # a slot's ring holds at most the window, whatever its position
    long.prefill(0, tokens_of(50, 3).tolist(), 4)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[0], pos[0] = 5, 50
    long.decode(tokens, pos)
    assert long.step_counters["window_positions_held_sum"] == 8
    assert long.step_counters["full_positions_held_sum"] == 51


def test_the_quarantine_scrub_zeroes_both_kinds():
    params = make_params()
    eng = make_engine(params)
    eng.prefill(2, tokens_of(11, 3).tolist(), 4)
    eng.poison_slot(2, 9)
    assert np.isnan(np.asarray(eng.cache["k_full"][0], np.float32)).any()
    eng.scrub_slot(2, 8)
    for name, leaves in eng.cache.items():
        for leaf in leaves:
            assert np.isfinite(np.asarray(leaf, np.float32)).all()
    assert not any(np.asarray(leaf[2]).any() for name in kv_cache.RING_LEAVES
                   for leaf in eng.cache[name])
    pages = eng._slot_pages[2]
    kept = np.asarray(eng.cache["k_full"][0][np.asarray(pages[:2])])
    assert kept.any() and not np.asarray(
        eng.cache["k_full"][0][np.asarray(pages[2:])]).any()


# -- what the engine refuses for this model --------------------------------------------


def test_each_refusal_raises_by_name():
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.spec import SpeculativeDecoder

    params = make_params()
    model = hybrid_model(SPEC)
    assert model.refuses == frozenset(FEATURES)
    kw = dict(model=model, batch_slots=2, max_seq=32, page_size=PAGE,
              num_pages=16, prefill_chunk=CHUNK)
    with pytest.raises(Refused, match="prefix_cache"):
        PagedInferenceEngine(params, **kw)  # the engine's default is on
    with pytest.raises(Refused, match="int8_pool"):
        PagedInferenceEngine(params, prefix_cache=False, cache_dtype=jnp.int8,
                             **kw)
    with pytest.raises(Refused, match="host_tier"):
        PagedInferenceEngine(params, prefix_cache=False, host_pages=4, **kw)
    mesh = create_mesh(MeshSpec(tensor=2), devices=jax.devices()[:2])
    with pytest.raises(Refused, match="tensor_mesh"):
        PagedInferenceEngine(params, prefix_cache=False, mesh=mesh, **kw)
    engine = PagedInferenceEngine(params, prefix_cache=False, **kw)
    with pytest.raises(Refused, match="verify"):
        SpeculativeDecoder(engine)


# -- the grouped-query decode kernel -----------------------------------------------------


@pytest.mark.parametrize("hkv,dk,dv", [(2, 12, 8), (4, 192, 128)])
def test_gqa_decode_kernel_matches_the_gather_path(hkv, dk, dv):
    """Interpret mode: the block-diagonal query form over folded pages reads
    what the plain gather reads, at positions on and off page edges."""
    hq, page, nb, B = 4 * hkv, 8, 5, 6
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (B, hq, dk), jnp.float32)
    k_pool = jax.random.normal(keys[1], (B * nb + 1, page, hkv * dk), jnp.float32)
    v_pool = jax.random.normal(keys[2], (B * nb + 1, page, hkv * dv), jnp.float32)
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(B * nb).reshape(B, nb) + 1,
        jnp.int32)
    pos = jnp.asarray([0, 7, 8, 23, 39, 17], jnp.int32)
    got = fd.decode_attention_gqa_paged(
        q, k_pool, v_pool, pos, tables, page_size=page, kernel="pallas")
    want = fd.decode_attention_gqa_paged(
        q, k_pool, v_pool, pos, tables, page_size=page, kernel="gather")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # and the gather path is plain attention over each slot's own pages
    b = 3
    seq_k = k_pool[tables[b]].reshape(nb * page, hkv, dk)[: int(pos[b]) + 1]
    seq_v = v_pool[tables[b]].reshape(nb * page, hkv, dv)[: int(pos[b]) + 1]
    plain = fd.gqa_attend(q[b][None], seq_k, seq_v,
                          jnp.ones((1, int(pos[b]) + 1), bool))[0]
    np.testing.assert_allclose(want[b], plain, atol=2e-5, rtol=1e-5)


def test_chunk_attention_follows_the_live_context(monkeypatch):
    """Blocks of the history are read up to the chunk's last position and no
    further: pages past it may hold anything."""
    monkeypatch.setattr(fd, "HISTORY_PAGES", 2)
    hkv, dk, dv, page, nb, C = 2, 12, 8, 4, 12, 8
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (C, 4 * hkv, dk), jnp.float32)
    k_pool = jax.random.normal(keys[1], (nb + 1, page, hkv * dk), jnp.float32)
    v_pool = jax.random.normal(keys[2], (nb + 1, page, hkv * dv), jnp.float32)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)
    posns = 13 + jnp.arange(C)
    got = fd.chunk_attention_gqa_paged(q, k_pool, v_pool, table, posns,
                                       page_size=page)
    s = nb * page
    want = fd.gqa_attend(
        q, k_pool[table].reshape(s, hkv, dk), v_pool[table].reshape(s, hkv, dv),
        jnp.arange(s)[None, :] <= posns[:, None])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    poisoned = k_pool.at[7:].set(jnp.nan)  # pages past position 20's block
    again = fd.chunk_attention_gqa_paged(q, poisoned, v_pool, table, posns,
                                         page_size=page)
    np.testing.assert_array_equal(again, got)


# -- the OPT block through the same description -------------------------------------------


OPT = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64, vocab_size=101,
           max_len=64)
#: what the parent commit's engine (b369881, before the description) served
#: for the run below, greedy
OPT_PARENT_TOKENS = [19, 66, 34, 93, 80, 83, 6, 86, 37]


def _opt_run(kernel):
    params = pt.init_params(jax.random.key(7), **OPT)
    eng = PagedInferenceEngine(
        params, num_heads=OPT["num_heads"], batch_slots=2, max_seq=48,
        page_size=4, num_pages=24, prefill_chunk=8, capture_logits=True,
        decode_kernel=kernel)
    prompt = tokens_of(13, 21).tolist()
    tok = eng.prefill(1, prompt, 9)
    prefill_logits = eng.last_prefill_logits
    seq, rows = prompt + [tok], []
    tokens, pos = np.zeros(2, np.int32), np.zeros(2, np.int32)
    for _ in range(8):
        tokens[1], pos[1] = seq[-1], len(seq) - 1
        out = eng.decode(tokens, pos)
        rows.append(eng.last_logits[1])
        seq.append(int(out[1]))
    return params, eng, prompt, seq, prefill_logits, np.stack(rows)


@pytest.mark.parametrize("kernel", ["gather", "xla"])
def test_opt_engine_output_is_bit_identical_to_the_bare_forwards(kernel):
    """The description adds nothing between the engine and the OPT block's
    two forwards: what the engine serves equals, bit for bit, what the
    forwards the parent's engine called give on the same cache."""
    params, eng, prompt, seq, prefill_logits, rows = _opt_run(kernel)
    assert eng.model.family == "opt" and not eng.model.slot_state
    assert eng._decode_jit.__wrapped__.__name__ == "_decode_fn"
    assert eng._chunk_jit.__wrapped__.__name__ == "_chunk_fn"
    cache = kv_cache.init_paged_cache(
        num_pages=24, num_layers=2, page_size=4, num_heads=4, head_dim=8)
    pages = eng._slot_pages[1]
    table = np.zeros(12, np.int32)
    table[: len(pages)] = pages
    logits = None
    for off in (0, 8):
        real = min(8, 13 - off)
        toks = np.zeros((1, 8), np.int32)
        toks[0, :real] = prompt[off: off + real]
        logits, cache = jax.jit(functools.partial(
            pt.forward_prefill_chunk, num_heads=4, page_size=4, kernel=kernel
        ))(params, jnp.asarray(toks), cache, jnp.asarray(table), jnp.int32(off))
    np.testing.assert_array_equal(prefill_logits, np.asarray(logits)[0, 4])
    tables = np.zeros((2, 12), np.int32)
    tables[1] = table
    step = jax.jit(lambda c, t, p: pt.forward_decode_paged(
        params, t, c, p, jnp.asarray(tables), num_heads=4, page_size=4,
        kernel=kernel))
    for i, row in enumerate(rows):
        step_logits, cache = step(
            cache, np.asarray([0, seq[13 + i]], np.int32),
            np.asarray([0, 13 + i], np.int32))
        np.testing.assert_array_equal(row, np.asarray(step_logits)[1])
    assert seq[13:] == OPT_PARENT_TOKENS
