"""State-space (Mamba) layers beside attention layers with one KV head and no
position signal, on the paged serving path: the fourth layer kind of
``models/hybrid_moe_transformer.py``, its chunk scan (``ops/ssm_scan.py``),
its per-slot state in ``serve/kv_cache.init_hybrid_cache`` and the engine's
description of it (``serve/served_model.hybrid_model``), all against the plain
reference the benchmark keeps (``benchmarks/families/jamba_reference.py``,
which imports nothing of the program).

The tiny size has every mechanism present: state-space layers 0, 1, 3 and 4
around the attention layer 2 (``attn_layer_period`` 4, offset 2); 16 states a
channel as published, 4 taps with a bias, the step, B and C each normed with
a scale that is not 1; 4 query heads over 1 KV head; chunks of 8 over pages of
4, so the state crosses chunk edges; the head tied to the embedding.

Tolerance: everything runs in float32 on the CPU, where the program and the
reference differ only in the order of float32 sums: ``ATOL`` 1e-4 on logits
of spread 1.7.  Every mechanism left out moves the logits, or the mixer's
output, by ``MOVED`` or more (the tests below that leave one out read so).
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import hashlib
import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models import hybrid_moe_transformer as hm
from distributeddeeplearning_tpu.ops import ssm_scan
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
ref = importlib.import_module("jamba_reference")

TINY = {
    "model_type": "jamba", "vocab_size": 97, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "num_hidden_layers": 5, "attn_layer_period": 4, "attn_layer_offset": 2,
    "intermediate_size": 64, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "num_experts": 1, "num_experts_per_tok": 1,
}
SPEC = hm.spec_from_config(TINY)
ARCH = ref.arch_of(TINY)
ATOL = 1e-4
MOVED = 1e-2  # what leaving a mechanism out moves the logits by, at least
PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
D, DI, N = 32, 64, 16
MAMBA_LEAVES = ("mamba_conv_state", "ssm_state")


def make_params(seed=0, spec=SPEC, std=0.3):
    params = hm.init_params(jax.random.key(seed), spec, std=std)
    keys = iter(jax.random.split(jax.random.key(seed + 1000), 64))
    for p in params["layers"]:
        for name in ("dt_norm", "b_norm", "c_norm"):  # scales that are not all 1
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


def states_of(eng, slot):
    return [np.asarray(leaf[slot]) for name in MAMBA_LEAVES
            for leaf in eng.cache[name]]


# -- the spec and the cache ---------------------------------------------------------


def test_the_spec_reads_the_published_keys():
    assert SPEC.attn_kinds == (hm.MAMBA, hm.MAMBA, hm.FULL, hm.MAMBA, hm.MAMBA)
    assert SPEC.ffn_kinds == (hm.DENSE,) * 5 and SPEC.tied_head
    assert (SPEC.k_dim, SPEC.kv_heads_full, SPEC.num_q_heads) == (8, 1, 4)
    assert not SPEC.rotate_full and SPEC.window == 0 and not SPEC.qk_norm
    assert SPEC.ssm == hm.SsmSpec(d_inner=64, d_state=16, d_conv=4, dt_rank=8,
                                  conv_bias=True, inner_norms=True)
    with pytest.raises(ValueError, match="num_experts 1"):
        hm.spec_from_config(dict(TINY, num_experts=16))
    with pytest.raises(ValueError, match="mamba_proj_bias"):
        hm.spec_from_config(dict(TINY, mamba_proj_bias=True))
    with pytest.raises(ValueError, match="SsmSpec"):
        dataclasses.replace(SPEC, ssm=None)
    with pytest.raises(ValueError, match="d_conv"):
        hm.SsmSpec(d_inner=64, d_state=16, d_conv=1, dt_rank=8)


def test_the_published_layer_pattern_puts_attention_at_7_and_21_only():
    with open(os.path.join(ROOT, "benchmarks", "configs", "jamba2-3b.json")) as f:
        cfg = json.load(f)
    spec = hm.spec_from_config(cfg)
    assert spec.layers_of(hm.FULL) == (7, 21)
    assert spec.layers_of(hm.MAMBA) == tuple(i for i in range(28)
                                             if i not in (7, 21))
    assert spec.ssm == hm.SsmSpec(d_inner=5120, d_state=16, d_conv=4,
                                  dt_rank=160, conv_bias=True)
    assert (spec.num_q_heads, spec.kv_heads_full, spec.k_dim) == (20, 1, 128)
    assert spec.d_ff == 8192 and spec.vocab_size == 65536 and spec.tied_head
    # every parameter, uncut: 3.029 B (the embedding once, as the head too)
    held = spec.vocab_size * spec.d_model + spec.d_model + sum(
        math.prod(shape) for layer in range(28)
        for shape in hm.layer_shapes(spec, layer).values())
    assert held == pytest.approx(3.029e9, rel=1e-3)
    # what a slot holds, and a position: by the cache the engine lays out
    cache = jax.eval_shape(lambda: hybrid_model(spec).init_cache(
        num_pages=4, page_size=128, batch_slots=2, dtype=jnp.bfloat16))
    assert kv_cache.slot_state_bytes(cache) == 2 * 9_318_400
    assert kv_cache.page_bytes(cache) == 128 * 1024
    assert {leaf.dtype for leaf in cache["ssm_state"]} == {jnp.dtype(jnp.float32)}


#: sha256 of the repr of every field but ``ssm``, sorted by name, of the spec
#: each configuration's file gives (the same before the state-space kind)
SPEC_DIGESTS = {"mimo-v2-flash": "bf4661ce33280ad3",
                "lfm2-8b-a1b": "2bda21f157a61a2b",
                "trinity-mini": "0d10d12e1d76be10"}


@pytest.mark.parametrize("name", sorted(SPEC_DIGESTS))
def test_the_other_hybrid_specs_and_caches_are_unchanged(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        spec = hm.spec_from_config(json.load(f))
    fields = {f: getattr(spec, f) for f in spec.__dataclass_fields__ if f != "ssm"}
    digest = hashlib.sha256(repr(sorted(fields.items())).encode()).hexdigest()
    assert digest[:16] == SPEC_DIGESTS[name]
    assert spec.ssm is None and hm.MAMBA not in spec.attn_kinds
    cache = jax.eval_shape(lambda: hybrid_model(spec).init_cache(
        num_pages=2, page_size=128, batch_slots=2, dtype=jnp.bfloat16))
    assert not cache["mamba_conv_state"] and not cache["ssm_state"]


def test_the_state_is_a_slot_s_and_does_not_grow_with_the_sequence():
    params = make_params()
    short = make_engine(params, max_seq=32)
    long = make_engine(params, max_seq=4096)
    lane = 4 * (3 * DI * 4 + N * DI * 4)  # 4 layers: 3 conv inputs, the state
    for eng in (short, long):
        assert not eng.cache["k_win"] and not eng.cache["conv_state"]
        assert len(eng.cache["k_full"]) == 1
        assert [leaf.shape for leaf in eng.cache["mamba_conv_state"]] == [
            (SLOTS, 3 * DI)] * 4
        assert [leaf.shape for leaf in eng.cache["ssm_state"]] == [
            (SLOTS, N * DI)] * 4
        assert kv_cache.slot_state_bytes(eng.cache) == SLOTS * lane
    assert short.page_bytes_each == PAGE * 2 * 8 * 4  # one attention layer, K and V
    assert long.required_pages(1000, 24) == 256
    # what a decode step counts: a live lane holds its state whole
    long.prefill(0, tokens_of(50, 3).tolist(), 4)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[0], pos[0] = 5, 50
    long.decode(tokens, pos)
    counted = long.step_counters
    assert counted["slot_state_bytes_held_sum"] == lane
    assert counted["kv_bytes_held_sum"] == 51 * 2 * 8 * 4


# -- the serving path against the reference's full forward -------------------------


def test_the_model_forward_matches_the_reference():
    params = make_params(3)
    toks = jnp.asarray(tokens_of(37, 5))
    got = hm.forward(params, toks, spec=SPEC)
    want = ref.forward(params, toks, ARCH)
    assert float(jnp.std(want)) > 0.5  # logits apart: a fault shows
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("length", [1, 3, 8, 9, 13, 21, 30])
def test_prefill_then_decode_matches_the_reference(length, kernel):
    """Prompts shorter than the convolution's reach (1, 3), that pad a chunk
    (3, 13, 21, 30: the padded rows must leave the state alone), end on a
    chunk edge (8), leave one real row in their last chunk (9) and carry the
    state across several chunk edges; then 10 decode steps. Logits, not
    tokens. The engine is the one the other lengths used, unscrubbed."""
    params, eng = shared(0, kernel)
    seq, got = serve_alone(eng, tokens_of(length, length), 10)
    np.testing.assert_allclose(got, reference_rows(params, seq, length),
                               atol=ATOL)


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
    assert report.slot_state_bytes_held_sum % (4 * (3 * DI + N * DI) * 4) == 0
    assert report.slot_state_bytes_held_sum > 0


def test_a_slot_reused_by_a_second_request_starts_from_zeros():
    """A state has no positions to mask by: the next occupant's first chunk
    starts from zeros whatever the slot holds, and no scrub runs between
    them (release calls none)."""
    params = make_params(4)
    eng = make_engine(params)
    eng.prefill(1, tokens_of(29, 7).tolist(), 4)
    assert all(s.any() for s in states_of(eng, 1))
    eng.release(1)
    assert all(s.any() for s in states_of(eng, 1))
    eng.prefill(1, tokens_of(6, 8).tolist(), 4)
    fresh = make_engine(params)
    fresh.prefill(1, tokens_of(6, 8).tolist(), 4)
    np.testing.assert_array_equal(eng.last_prefill_logits,
                                  fresh.last_prefill_logits)
    for a, b in zip(states_of(eng, 1), states_of(fresh, 1)):
        np.testing.assert_array_equal(a, b)


def test_dead_lanes_keep_their_states_through_decode_steps():
    """A released slot and a slot whose prompt is still being prefilled are
    not live: a decode step of another lane leaves both their states as
    they were, to the bit."""
    params = make_params(5)
    eng = make_engine(params)
    eng.prefill(0, tokens_of(19, 2).tolist(), 4)
    eng.release(0)
    task = eng.prefill_begin(2, tokens_of(27, 3).tolist(), 4)
    assert eng.prefill_step(task) is None  # one chunk of four
    tok = eng.prefill(1, tokens_of(11, 4).tolist(), 8)
    before = {slot: states_of(eng, slot) for slot in (0, 2)}
    lane1 = states_of(eng, 1)
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for k in range(5):
        tokens[1], pos[1] = tok, 11 + k
        tok = int(eng.decode(tokens, pos)[1])
    for slot in (0, 2):
        for a, b in zip(states_of(eng, slot), before[slot]):
            np.testing.assert_array_equal(a, b)
    assert all((a != b).any() for a, b in zip(states_of(eng, 1), lane1))


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
    assert not any(s.any() for s in states_of(eng, 2))
    assert all(s.any() for s in states_of(eng, 0))


# -- the state carried across an edge, and each mechanism, left out ----------------------


def _zero_states(eng):
    eng._cache = dict(eng.cache, **{name: tuple(
        jnp.zeros_like(leaf) for leaf in eng.cache[name])
        for name in MAMBA_LEAVES})


@pytest.mark.parametrize("where", ["between_chunks", "before_decode"])
def test_the_state_carried_across_an_edge_left_out_moves_the_logits(where):
    params, eng = shared(0)
    prompt = tokens_of(21, 21)
    if where == "between_chunks":
        seq, got = serve_alone(eng, prompt, 0, between_chunks=_zero_states)
        want = reference_rows(params, seq, len(prompt))
    else:
        tok = eng.prefill(1, prompt.tolist(), 4)
        _zero_states(eng)
        tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tokens[1], pos[1] = tok, len(prompt)
        eng.decode(tokens, pos)
        eng.release(1)
        got = eng.last_logits[1][None]
        want = np.asarray(ref.forward(
            params, jnp.asarray(list(prompt) + [tok]), ARCH))[-1:]
    assert np.abs(got - want).max() > MOVED


def _numpy_mixer(p, h, *, norms=True, d=True, bias=True, gate=True):
    """The mixer's equations in float64 numpy, each mechanism a switch."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h = np.asarray(h, np.float64)
    T = h.shape[0]
    xz = h @ p["w_in"]
    x, z = xz[:, :DI], xz[:, DI:]
    c = np.zeros_like(x)
    for t in range(T):
        for j in range(4):
            if t - 3 + j >= 0:
                c[t] += p["conv_w"][j] * x[t - 3 + j]
    xc = c + (p["conv_b"] if bias else 0.0)
    xc = xc / (1 + np.exp(-xc))
    dbc = xc @ p["w_x"]
    dt, b, cc = dbc[:, :8], dbc[:, 8:24], dbc[:, 24:]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * g

    if norms:
        dt, b, cc = rms(dt, p["dt_norm"]), rms(b, p["b_norm"]), rms(cc, p["c_norm"])
    dt = np.log1p(np.exp(dt @ p["w_dt"] + p["b_dt"]))
    a = -np.exp(p["A_log"])
    s = np.zeros((N, DI))
    y = np.zeros_like(xc)
    for t in range(T):
        s = np.exp(dt[t] * a) * s + (dt[t] * xc[t]) * b[t][:, None]
        y[t] = (s * cc[t][:, None]).sum(0) + (p["D"] * xc[t] if d else 0.0)
    if gate:
        y = y * (z / (1 + np.exp(-z)))
    return y @ p["w_out"]


def _mixer_case():
    p = make_params(6)["layers"][0]
    h = jax.random.normal(jax.random.key(4), (13, D), jnp.float32)
    got = np.asarray(hm.mamba_mixer(p, h, spec=SPEC, state=hm.MambaState(
        lambda x: tuple(jnp.pad(x, ((back, 0), (0, 0)))[:13]
                        for back in (3, 2, 1, 0)),
        lambda *a: ssm_scan.ssm_scan_reference(*a, jnp.zeros((N, DI)))[0])))
    return p, h, got


def test_the_mixer_is_the_published_equations():
    p, h, got = _mixer_case()
    np.testing.assert_allclose(got, _numpy_mixer(p, h), atol=1e-4)
    # the reference's mixer is the same
    np.testing.assert_allclose(got, ref._mamba(p, h, ARCH, False), atol=1e-4)


@pytest.mark.parametrize("left_out", ["norms", "d", "bias", "gate"])
def test_each_mixer_mechanism_left_out_moves_the_output(left_out):
    """The step's, B's and C's norms, the skip term D x, the convolution's
    bias and the gate silu(z): the equations without any one of them give
    an output the program's mixer is not."""
    p, h, got = _mixer_case()
    bare = _numpy_mixer(p, h, **{left_out: False})
    assert np.abs(got - bare).max() > MOVED * np.abs(got).max()


@pytest.mark.parametrize("left_out", ["inner_norms", "conv_bias", "D"])
def test_the_model_without_a_mechanism_moves_the_logits(left_out):
    params = make_params(3)
    toks = jnp.asarray(tokens_of(37, 5))
    want = ref.forward(params, toks, ARCH)
    spec = SPEC
    if left_out == "D":
        params = copy.copy(params)
        params["layers"] = [{**p, "D": jnp.zeros_like(p["D"])} if "D" in p
                            else p for p in params["layers"]]
    else:
        spec = dataclasses.replace(SPEC, ssm=dataclasses.replace(
            SPEC.ssm, **{left_out: False}))
    got = hm.forward(params, toks, spec=spec)
    assert float(jnp.abs(got - want).max()) > MOVED


# -- the chunk scan kernel ------------------------------------------------------------


def _scan_case(rows, di=384, n=N, seed=0, zero_state=False):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.0, 0.3, (rows, di)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (n, di)), jnp.float32)
    s0 = jnp.zeros((n, di)) if zero_state else f32(n, di)
    return dt, f32(rows, di), f32(rows, n), f32(rows, n), a, f32(di), s0


@pytest.mark.parametrize("rows", [1, 7, 128, 512])
def test_the_chunk_scan_kernel_is_the_plain_scan(rows):
    """In interpret mode, over three blocks of 128 channels (the grid's
    edges), from a carried state that is not zero; rows not a multiple of
    eight are padded inside with steps of 0."""
    case = _scan_case(rows)
    y, s = ssm_scan.ssm_scan_chunk(*case, block=128)
    y_ref, s_ref = ssm_scan.ssm_scan_reference(*case)
    assert y.shape == (rows, 384) and s.shape == (N, 384)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(s, s_ref, rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(s - case[-1]).max()) > 1e-2  # the state moved


def test_rows_with_a_step_of_zero_leave_the_state_as_it_was():
    dt, x, b, c, a, d, s0 = _scan_case(16, seed=3)
    _, s8 = ssm_scan.ssm_scan_chunk(dt[:8], x[:8], b[:8], c[:8], a, d, s0,
                                    block=128)
    padded = dt.at[8:].set(0.0)
    _, s16 = ssm_scan.ssm_scan_chunk(padded, x, b, c, a, d, s0, block=128)
    # equal to the rounding of two differently compiled programs
    np.testing.assert_allclose(s8, s16, rtol=1e-6, atol=1e-6)
    # and a chunk cut in two carries its state across the cut
    y_a, s_a = ssm_scan.ssm_scan_chunk(dt[:5], x[:5], b[:5], c[:5], a, d, s0,
                                       block=128)
    y_b, s_b = ssm_scan.ssm_scan_chunk(dt[5:], x[5:], b[5:], c[5:], a, d, s_a,
                                       block=128)
    y, s = ssm_scan.ssm_scan_chunk(dt, x, b, c, a, d, s0, block=128)
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b]), y, atol=1e-5)
    np.testing.assert_allclose(s_b, s, atol=1e-5)


def test_the_chunk_program_calls_the_scan_kernel():
    params = make_params()
    cache = hybrid_model(SPEC).init_cache(num_pages=8, page_size=PAGE,
                                          batch_slots=SLOTS, dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, c: hm.forward_prefill_chunk(
        p, jnp.zeros((1, CHUNK), jnp.int32), c, jnp.zeros(4, jnp.int32),
        jnp.int32(0), jnp.int32(1), jnp.int32(5), spec=SPEC,
        page_size=PAGE))(params, cache)
    names = [e.params.get("name") for e in jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert names == ["ssm_scan_chunk_float32"] * 4


# -- what the engine refuses for this model --------------------------------------------


def test_each_refusal_raises_by_name_with_the_state_s_reason():
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.spec import SpeculativeDecoder

    params = make_params()
    model = hybrid_model(SPEC)
    assert model.refuses == frozenset(FEATURES) == frozenset(model.reasons)
    kw = dict(model=model, batch_slots=2, max_seq=32, page_size=PAGE,
              num_pages=16, prefill_chunk=CHUNK)
    with pytest.raises(Refused, match="prefix_cache.*state-space layers' state"):
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
    with pytest.raises(Refused, match="verify.*state-space layers' state"):
        SpeculativeDecoder(engine)
