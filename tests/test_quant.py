"""Int8 quantization subsystem: QTensor math, weight PTQ, int8 KV cache.

The load-bearing guarantees:

- ``quantize``/``dequantize`` round-trip within the 8-bit grid's step and
  ``qdot`` tracks the f32 matmul closely (int8 dot_general + f32 rescale);
- a quantized params pytree flows through the existing forwards (the
  negative-axis QTensor metadata survives the layer scan) and the logits
  stay close to f32;
- the int8 KV cache — dense AND paged — produces the same greedy tokens
  as the f32 cache on serve traffic (the acceptance gate: >= 99% of
  positions), with ``kv_bytes`` (values + scales) <= 55% of the f32
  figure at identical pool geometry;
- byte accounting sums EVERY cache leaf, so scale tensors are charged;
- ``Checkpointer.restore_params(quantize_weights="int8")`` materializes
  the quantized pytree straight from an f32 checkpoint;
- ``bench.py --quant --steps-cap`` runs end-to-end on CPU (fast tier).
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
    init_params,
)
from distributeddeeplearning_tpu.quant import (
    QTensor,
    bf16_matmul_params,
    calibrate_params,
    dequantize,
    dequantize_kv,
    params_dtype,
    qdot,
    qmatmul,
    quantize,
    quantize_kv,
    quantize_params,
)
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    PagedInferenceEngine,
    cache_bytes,
    init_cache,
    init_paged_cache,
    page_bytes,
    synthetic_requests,
)

CFG = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128, vocab_size=61,
           max_len=96)
HEADS = CFG["num_heads"]
HEAD_DIM = CFG["d_model"] // HEADS


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), **CFG)


# --------------------------------------------------------------------------
# QTensor / qdot
# --------------------------------------------------------------------------

def test_quantize_roundtrip_within_grid_step():
    w = jax.random.normal(jax.random.key(1), (32, 48)) * 0.1
    qt = quantize(w)
    assert qt.values.dtype == jnp.int8
    assert qt.scales.shape == (1, 48)  # keepdims per-output-channel
    # absmax symmetric grid: error bounded by half a step per channel
    step = np.asarray(qt.scales)[0]  # [48]
    err = np.abs(np.asarray(dequantize(qt)) - np.asarray(w))
    assert (err <= step[None, :] * 0.5 + 1e-7).all()


def test_quantize_block_scales_shape_and_roundtrip():
    w = jax.random.normal(jax.random.key(2), (32, 48)) * 0.1
    qb = quantize(w, block=8)
    assert qb.scales.shape == (4, 1, 48)  # 32/8 blocks, keepdims, per-chan
    err = float(jnp.abs(dequantize(qb) - w).max())
    # block scales are never looser than whole-axis absmax scales
    assert err <= float(jnp.abs(dequantize(quantize(w)) - w).max()) + 1e-7


def test_qdot_matches_f32_matmul():
    w = jax.random.normal(jax.random.key(3), (64, 96)) * 0.05
    x = jax.random.normal(jax.random.key(4), (3, 7, 64))
    qt = quantize(w)
    out_q = np.asarray(qdot(x, qt))
    out_f = np.asarray(x @ w)
    rel = np.abs(out_q - out_f).mean() / np.abs(out_f).mean()
    assert rel < 0.02, f"int8 matmul drifted {rel:.3%} from f32"


def test_qdot_lowers_to_int8_dot_general():
    """The compute path really is int8: the jaxpr contains a dot_general
    whose operands are int8 with an int32 accumulator — not a dequantize
    followed by an f32 dot."""
    w = jax.random.normal(jax.random.key(5), (16, 8)) * 0.1
    qt = quantize(w)
    x = jnp.ones((4, 16))
    jaxpr = jax.make_jaxpr(lambda a: qdot(a, qt))(x)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots, "no dot_general in qdot"
    (dot,) = dots
    assert all(str(v.aval.dtype) == "int8" for v in dot.invars)
    assert str(dot.outvars[0].aval.dtype) == "int32"


def _exact_operands():
    """``x`` [5, 64] float32 with more mantissa than bf16 keeps, ``w``
    [64, 48] of small integers: every product of the ROUNDED operands is
    an integer and every partial sum stays under 2**24, so a float32
    accumulation gives one answer in any order, and it is neither the
    unrounded product nor one a bf16 result could hold."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(-1000, 1001, (5, 64)), jnp.float32)
    w = jnp.asarray(rng.integers(-8, 9, (64, 48)), jnp.float32)
    return x, w


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_qmatmul_bf16_weight_is_the_product_of_rounded_operands(jitted):
    """f32 ``x`` on a bf16 ``w``: to every bit the product of ``x`` and
    ``w`` both rounded to bf16, accumulated and returned in float32."""
    x, w = _exact_operands()
    mm = jax.jit(qmatmul) if jitted else qmatmul
    got = mm(x, w.astype(jnp.bfloat16))
    want = np.asarray(x.astype(jnp.bfloat16), np.float64) @ np.asarray(
        w, np.float64
    )
    assert got.dtype == jnp.float32 and got.shape == (5, 48)
    assert (np.asarray(got, np.float64) == want).all()
    # the test can tell: x really lost mantissa, and the sums need more
    # of it than a bf16 result has
    assert (np.asarray(x @ w) != np.asarray(got)).any()
    assert (np.asarray(got.astype(jnp.bfloat16), np.float64) != want).any()


def test_qmatmul_bf16_weight_lowers_to_one_bf16_dot():
    """One dot_general, bf16 x bf16 -> f32, whose weight operand is the
    argument itself: nothing rounds (or widens) ``w`` inside the call."""
    x, w = _exact_operands()
    jaxpr = jax.make_jaxpr(qmatmul)(x, w.astype(jnp.bfloat16)).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns] == [
        "convert_element_type", "dot_general",
    ]
    convert, dot = jaxpr.eqns
    assert convert.invars[0] is jaxpr.invars[0]  # x, and only x
    assert dot.invars[1] is jaxpr.invars[1]
    assert [str(v.aval.dtype) for v in dot.invars] == ["bfloat16"] * 2
    assert str(dot.outvars[0].aval.dtype) == "float32"
    # a stacked activation contracts its last axis with w's first
    out = qmatmul(jnp.stack([x, x])[:, None], w.astype(jnp.bfloat16))
    assert out.shape == (2, 1, 5, 48)


@pytest.mark.parametrize("case", ["f32_f32", "bf16_bf16", "bf16_f32", "int8"])
def test_qmatmul_other_operands_run_the_line_they_ran(case):
    """The three cases that were there are the expressions they were,
    bit for bit: ``x @ w`` (f32 both; bf16 both, the bf16 train step;
    also bf16 ``x`` on f32 ``w``) and ``qdot`` for a QTensor."""
    x, w = _exact_operands()
    x = x / 1000 + 0.013
    w = w * 0.02
    if case == "int8":
        qt = quantize(w)
        got, want = qmatmul(x, qt), qdot(x, qt)
    else:
        xd, wd = (
            jnp.bfloat16 if d == "bf16" else jnp.float32
            for d in case.split("_")
        )
        x, w = x.astype(xd), w.astype(wd)
        got, want = qmatmul(x, w), x @ w
        jaxpr = jax.make_jaxpr(qmatmul)(x, w).jaxpr
        assert str(jaxpr) == str(jax.make_jaxpr(jnp.matmul)(x, w).jaxpr)
    assert got.dtype == want.dtype
    assert (np.asarray(got) == np.asarray(want)).all()


def test_qtensor_is_a_pytree_and_scan_slices_it():
    """A stacked [L, K, N] QTensor scanned by lax.scan yields per-layer
    [K, N] QTensors whose negative-axis metadata is still valid."""
    w = jax.random.normal(jax.random.key(6), (3, 8, 10)) * 0.1
    qt = quantize(w)  # axis=-2 on the stacked leaf
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    assert len(leaves) == 2
    assert jax.tree_util.tree_unflatten(treedef, leaves).axis == qt.axis

    def body(carry, layer_qt):
        return carry + jnp.sum(dequantize(layer_qt)), None

    total, _ = jax.lax.scan(body, jnp.float32(0), qt)
    assert np.isclose(float(total), float(dequantize(qt).sum()), atol=1e-3)


def test_quantize_kv_per_position_per_head():
    x = jax.random.normal(jax.random.key(7), (5, HEADS, HEAD_DIM))
    vals, scales = quantize_kv(x)
    assert vals.dtype == jnp.int8 and vals.shape == x.shape
    assert scales.shape == (5, HEADS)  # one scale per (position, head)
    err = np.abs(np.asarray(dequantize_kv(vals, scales)) - np.asarray(x))
    assert (err <= np.asarray(scales)[..., None] * 0.5 + 1e-7).all()


# --------------------------------------------------------------------------
# weight PTQ / calibration
# --------------------------------------------------------------------------

def test_quantize_params_leaves_and_passthrough(params):
    qp = quantize_params(params)
    for name in ("qkv", "proj", "w_in", "w_out"):
        assert isinstance(qp["blocks"][name], QTensor)
        assert qp["blocks"][name].shape == params["blocks"][name].shape
    assert isinstance(qp["head"], QTensor)
    # embeddings / position table / layer norms stay f32 (and identical)
    assert qp["embed"] is params["embed"]
    assert qp["pos"] is params["pos"]
    assert qp["blocks"]["ln1"] is params["blocks"]["ln1"]
    assert params_dtype(params) == "float32"
    assert params_dtype(qp) == "int8"
    with pytest.raises(ValueError, match="already quantized"):
        quantize_params(qp)


def test_bf16_matmul_params_rounds_the_f32_matmul_leaves(params):
    """The leaves ``quantize_params`` quantizes become ``astype(bf16)``
    of themselves; every other leaf is the very array it was."""
    held = bf16_matmul_params(params)
    changed = [("blocks", n) for n in ("qkv", "proj", "w_in", "w_out")]
    changed.append(("head",))
    for path in changed:
        old = params[path[0]] if len(path) == 1 else params["blocks"][path[1]]
        new = held[path[0]] if len(path) == 1 else held["blocks"][path[1]]
        assert new.dtype == jnp.bfloat16 and new.shape == old.shape
        assert (np.asarray(new) == np.asarray(old.astype(jnp.bfloat16))).all()
    for name in ("embed", "pos"):
        assert held[name] is params[name]
    for name in ("ln1", "ln2"):
        assert held["blocks"][name] is params["blocks"][name]
    assert set(held) == set(params)
    assert set(held["blocks"]) == set(params["blocks"])
    # the caller's tree is not written to
    assert params["head"].dtype == jnp.float32
    assert params["blocks"]["qkv"].dtype == jnp.float32
    # provenance: the first leaf is a LayerNorm scale, so the dtype the
    # caller's weights are stored in still reads float32
    assert params_dtype(held) == "float32"


def _other_model_tree():
    # the shape of models/hybrid_moe_transformer's tree: no ``blocks``
    bf = jnp.bfloat16
    return {"embed": jnp.ones((7, 4), bf), "final_norm": jnp.ones((4,), bf),
            "layers": [{"wq": jnp.ones((4, 4), bf)}],
            "head": jnp.ones((4, 7), bf)}


@pytest.mark.parametrize("tree", ["int8", "bf16", "copied", "other_model"])
def test_bf16_matmul_params_passes_everything_else_through(tree, params):
    """QTensor leaves, bf16 leaves, its own output (idempotent) and the
    tree of a model with no ``blocks`` come back as the same object."""
    given = {
        "int8": lambda: quantize_params(params),
        "bf16": lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params),
        "copied": lambda: bf16_matmul_params(params),
        "other_model": _other_model_tree,
    }[tree]()
    assert bf16_matmul_params(given) is given


def test_bf16_matmul_params_mixed_tree_rounds_only_what_is_f32(params):
    """Told apart a leaf, not a tree: an int8 head beside f32 stacks."""
    mixed = {**params, "head": quantize_params(params)["head"]}
    held = bf16_matmul_params(mixed)
    assert held["head"] is mixed["head"]
    assert held["blocks"]["w_in"].dtype == jnp.bfloat16
    assert held["blocks"]["ln2"] is params["blocks"]["ln2"]


def test_bf16_matmul_params_eval_shape_skeleton(params):
    """``jax.eval_shape`` over the transform gives the avals of what it
    makes (how a program is sized for the engine's tree with no weights
    in hand), and one traced call covers every leaf it rounds."""
    skeleton = jax.eval_shape(bf16_matmul_params, params)
    held = bf16_matmul_params(params)
    assert jax.tree_util.tree_structure(skeleton) == (
        jax.tree_util.tree_structure(held)
    )
    for a, b in zip(jax.tree_util.tree_leaves(skeleton),
                    jax.tree_util.tree_leaves(held)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    eqns = jax.make_jaxpr(bf16_matmul_params)(params).jaxpr.eqns
    assert [e.primitive.name for e in eqns] == ["jit"]
    assert len(eqns[0].outvars) == 5


def test_quantized_forward_tracks_f32(params):
    qp = quantize_params(params)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(1, CFG["vocab_size"], (2, 12))
    )
    lf = forward(params, toks, num_heads=HEADS)
    lq = forward(qp, toks, num_heads=HEADS)
    # the random-init model's logits are nearly flat (spread ~1e-2), so
    # the meaningful gate is MAE against that spread; argmax agreement is
    # only loosely pinned here (near-ties flip on ulp-level noise — the
    # >= 99% greedy gates live in the KV-cache tests, where margins are
    # the serving workload's own)
    spread = float(jnp.abs(lf - lf.mean(-1, keepdims=True)).mean())
    assert float(jnp.abs(lf - lq).mean()) < max(0.05 * spread, 1e-4)
    assert float((lf.argmax(-1) == lq.argmax(-1)).mean()) >= 0.9


def test_calibrate_params_reports_fidelity(params):
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14]]
    qp, rep = calibrate_params(params, prompts, num_heads=HEADS)
    assert params_dtype(qp) == "int8"
    assert rep.num_prompts == 3
    assert rep.num_positions == sum(len(p) for p in prompts)
    assert rep.logit_mae <= rep.logit_mae_max
    assert 0.0 <= rep.greedy_agreement <= 1.0
    assert rep.logit_mae < 1e-3  # tiny vs any usable logit spread
    # percentile observer path (clips outliers; still close)
    qp2, rep2 = calibrate_params(
        params, prompts, num_heads=HEADS, method="percentile",
        percentile=99.0,
    )
    assert rep2.percentile == 99.0
    assert rep2.greedy_agreement >= 0.9


def test_restore_params_materializes_int8(tmp_path, params):
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer

    class _State:
        step = jnp.int32(7)
        params = None
        opt_state = {"m": jnp.zeros(3)}
        batch_stats = {"n": jnp.zeros(1)}

    st = _State()
    st.params = params
    ckpt = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
    try:
        assert ckpt.save(7, st)
        restored, step = ckpt.restore_params(quantize_weights="int8")
    finally:
        ckpt.close()
    assert step == 7
    assert params_dtype(restored) == "int8"
    assert isinstance(restored["head"], QTensor)
    np.testing.assert_array_equal(restored["embed"], params["embed"])
    with pytest.raises(ValueError, match="unsupported"):
        ckpt2 = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
        try:
            ckpt2.restore_params(quantize_weights="int4")
        finally:
            ckpt2.close()


# --------------------------------------------------------------------------
# int8 KV cache: byte accounting
# --------------------------------------------------------------------------

def test_cache_bytes_counts_scale_leaves():
    kw = dict(num_layers=2, num_heads=HEADS, head_dim=HEAD_DIM)
    f32 = init_cache(batch_slots=2, max_seq=16, dtype=jnp.float32, **kw)
    q = init_cache(batch_slots=2, max_seq=16, dtype=jnp.int8, **kw)
    assert set(q) == {"k", "v", "k_scale", "v_scale"}
    n = 2 * 2 * 16 * HEADS * HEAD_DIM  # elements per leaf (k or v)
    assert cache_bytes(f32) == 2 * n * 4
    assert cache_bytes(q) == 2 * n * 1 + 2 * (n // HEAD_DIM) * 4
    ratio = cache_bytes(q) / cache_bytes(f32)
    assert ratio == (1 + 4 / HEAD_DIM) / 4
    assert ratio <= 0.55


def test_page_bytes_counts_scale_leaves():
    kw = dict(num_layers=2, page_size=4, num_heads=HEADS, head_dim=HEAD_DIM)
    f32 = init_paged_cache(num_pages=6, dtype=jnp.float32, **kw)
    q = init_paged_cache(num_pages=6, dtype=jnp.int8, **kw)
    assert cache_bytes(q) == 7 * page_bytes(q)  # pages + scratch
    per_tok_head = HEAD_DIM * 1 + 4  # int8 vector + one f32 scale
    assert page_bytes(q) == 2 * 2 * 4 * HEADS * per_tok_head
    assert page_bytes(q) / page_bytes(f32) <= 0.55


# --------------------------------------------------------------------------
# int8 KV cache: greedy agreement vs f32, both layouts
# --------------------------------------------------------------------------

def _run_traffic(engine, requests, max_new):
    res, rep = ContinuousBatchingScheduler(
        engine, max_new_tokens=max_new
    ).run(list(requests))
    return {r.uid: r.tokens for r in res}, rep


def _agreement(a, b):
    tot = match = 0
    for uid in a:
        for x, y in zip(a[uid], b[uid]):
            tot += 1
            match += int(x == y)
    return match / tot


def test_int8_dense_cache_matches_f32_greedy(params):
    reqs = synthetic_requests(
        8, vocab_size=CFG["vocab_size"], max_prompt=12, min_prompt=4,
        rng=np.random.default_rng(0),
    )
    kw = dict(num_heads=HEADS, batch_slots=2, max_seq=32,
              prefill_attention="dense", rng=jax.random.key(1))
    tf, rf = _run_traffic(InferenceEngine(params, **kw), reqs, 8)
    tq, rq = _run_traffic(
        InferenceEngine(params, cache_dtype=jnp.int8, **kw), reqs, 8
    )
    assert _agreement(tf, tq) >= 0.99
    assert rq.kv_dtype == "int8" and rf.kv_dtype == "float32"
    assert rq.kv_bytes / rf.kv_bytes <= 0.55


def test_int8_paged_cache_matches_f32_greedy(params):
    reqs = synthetic_requests(
        8, vocab_size=CFG["vocab_size"], max_prompt=24, min_prompt=6,
        rng=np.random.default_rng(0),
    )
    kw = dict(num_heads=HEADS, batch_slots=2, max_seq=48, page_size=8,
              prefill_chunk=16, rng=jax.random.key(1))
    tf, rf = _run_traffic(PagedInferenceEngine(params, **kw), reqs, 12)
    eq = PagedInferenceEngine(params, cache_dtype=jnp.int8, **kw)
    tq, rq = _run_traffic(eq, reqs, 12)
    assert _agreement(tf, tq) >= 0.99
    assert rq.kv_dtype == "int8"
    assert rq.kv_layout == "paged"
    assert rq.kv_bytes / rf.kv_bytes <= 0.55
    assert rq.kv_bytes_peak / rf.kv_bytes_peak <= 0.55
    eq.allocator.check()  # page bookkeeping survived quantized traffic


def test_int8_paged_prefix_sharing_still_exact(params):
    """Prefix-cache hits under the int8 pool: a shared page's int8 values
    AND scales are reused, so a hit decodes identically to a recompute.
    The shared prefix (12 tokens = 3 pages) is deliberately NOT a
    multiple of prefill_chunk (16), so the hit path starts mid-chunk —
    pinning that quantized prefill is chunk-ALIGNMENT-invariant (an
    exact-own-chunk attention window would break exactly this)."""
    reqs = synthetic_requests(
        6, vocab_size=CFG["vocab_size"], max_prompt=12, min_prompt=4,
        shared_prefix_len=12, rng=np.random.default_rng(3),
    )
    kw = dict(num_heads=HEADS, batch_slots=2, max_seq=48, page_size=4,
              prefill_chunk=16, rng=jax.random.key(1),
              cache_dtype=jnp.int8)
    hit = PagedInferenceEngine(params, **kw)
    t_hit, rep_hit = _run_traffic(hit, reqs, 6)
    miss = PagedInferenceEngine(params, prefix_cache=False, **kw)
    t_miss, rep_miss = _run_traffic(miss, reqs, 6)
    assert rep_hit.prefix_hit_rate > 0.0
    assert rep_miss.prefix_hit_rate == 0.0
    assert t_hit == t_miss
    hit.allocator.check()


def test_int8_dense_cache_shards_over_mesh(params):
    """Sharded dense engine with the int8 cache: the scale leaves shard
    like their values (slots over data axes, heads over tensor) and the
    run completes with sharding preserved through donated decode."""
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.serve import Request

    mesh = create_mesh(MeshSpec(), devices=jax.devices()[:2])
    engine = InferenceEngine(
        params, num_heads=HEADS, batch_slots=4, max_seq=24, mesh=mesh,
        prefill_attention="dense", cache_dtype=jnp.int8,
    )
    assert engine.cache["k"].dtype == jnp.int8
    assert engine.cache["k_scale"].sharding.spec[0] == ("data", "fsdp")
    reqs = [
        Request(uid=f"r{i}", prompt=[3 + i, 7, 11])
        for i in range(6)
    ]
    results, report = ContinuousBatchingScheduler(
        engine, max_new_tokens=3
    ).run(reqs)
    assert len(results) == 6
    assert report.kv_dtype == "int8"
    assert engine.cache["k_scale"].sharding.spec[0] == ("data", "fsdp")


def test_int8_weights_plus_kv_serve_end_to_end(params):
    qp = quantize_params(params)
    reqs = synthetic_requests(
        4, vocab_size=CFG["vocab_size"], max_prompt=12, min_prompt=4,
        rng=np.random.default_rng(5),
    )
    eng = PagedInferenceEngine(
        qp, num_heads=HEADS, batch_slots=2, max_seq=32, page_size=8,
        prefill_chunk=8, rng=jax.random.key(1), cache_dtype=jnp.int8,
    )
    toks, rep = _run_traffic(eng, reqs, 6)
    assert all(len(t) == 6 for t in toks.values())
    assert rep.weights_dtype == "int8" and rep.kv_dtype == "int8"
    d = rep.to_dict()
    assert d["weights_dtype"] == "int8"  # ServeReport plumbs provenance


# --------------------------------------------------------------------------
# CI smoke: the quant bench path end-to-end through bench.py on CPU
# --------------------------------------------------------------------------

@pytest.mark.timeout(240)
def test_bench_quant_cpu_smoke(tmp_path):
    """Fast tier-1 smoke: bench.py --quant with a hard --steps-cap so the
    five-engine comparison (flash + gather exhibits) + fidelity probe
    can never hang CI."""
    report = tmp_path / "quant.json"
    proc = subprocess.run(
        [
            sys.executable, "bench.py", "--quant", "--small",
            "--seq-len", "12", "--serve-requests", "6",
            "--batch-slots", "2", "--max-new-tokens", "4",
            "--page-size", "4", "--prefill-chunk", "8",
            "--steps-cap", "50", "--report", str(report),
        ],
        capture_output=True, text=True, timeout=220,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["value"] <= 0.55  # int8 kv bytes ratio, scales included
    assert set(line["configs"]) == {
        "f32", "kv_int8", "kv_w_int8",
        # PR 12: the legacy gather exhibits ride in the same artifact
        "f32_gather", "kv_int8_gather",
    }
    assert line["configs"]["kv_int8"]["kv_dtype"] == "int8"
    assert line["configs"]["kv_w_int8"]["weights_dtype"] == "int8"
    assert line["fidelity_probe"]["kv_int8"]["positions"] > 0
    assert report.exists()
