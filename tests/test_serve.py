"""Serving subsystem (serve/): KV cache, engine, continuous batching.

The load-bearing guarantee is decode correctness: token-t logits from the
KV-cached decode path must match a fresh full-sequence forward at position
t — bit-for-bit the same math, different dataflow.  Everything else
(slot release/reuse, EOS, sharding) is exercised against that oracle.
"""

from __future__ import annotations

import io
import json
import sys
import types
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward,
    forward_decode,
    forward_prefill,
    init_params,
)
from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
from distributeddeeplearning_tpu.quant import bf16_matmul_params
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    PagedInferenceEngine,
    Request,
    cache_bytes,
    init_cache,
    insert_sequence,
)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CFG = dict(num_layers=3, d_model=32, num_heads=4, d_ff=64, vocab_size=61,
           max_len=32)
HEADS = CFG["num_heads"]
HEAD_DIM = CFG["d_model"] // HEADS


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), **CFG)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.default_rng(0).integers(1, CFG["vocab_size"], (2, 12)),
        jnp.int32,
    )


def _naive_greedy(params, prompt, n):
    """Oracle: greedy generation by full-forward recompute every step."""
    toks = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([toks], jnp.int32),
                         num_heads=HEADS)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_prefill_matches_forward(params, tokens):
    """forward_prefill is forward + captured per-layer K/V."""
    want = forward(params, tokens, num_heads=HEADS)
    logits, k, v = forward_prefill(params, tokens, num_heads=HEADS)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-6)
    b, s = tokens.shape
    assert k.shape == (b, CFG["num_layers"], s, HEADS, HEAD_DIM)
    assert v.shape == k.shape


def test_decode_matches_full_forward_at_every_position(params, tokens):
    """Acceptance pin: decode-step-t logits == full forward at position t,
    for every t, starting from an empty cache."""
    b, s = tokens.shape
    full = np.asarray(forward(params, tokens, num_heads=HEADS))
    cache = init_cache(
        batch_slots=b, num_layers=CFG["num_layers"], max_seq=16,
        num_heads=HEADS, head_dim=HEAD_DIM,
    )
    for t in range(s):
        logits, cache = forward_decode(
            params, tokens[:, t], cache, jnp.full((b,), t, jnp.int32),
            num_heads=HEADS,
        )
        np.testing.assert_allclose(
            np.asarray(logits), full[:, t], atol=1e-5,
            err_msg=f"decode diverged from full forward at position {t}",
        )


def test_prefill_then_decode_matches_full_forward(params, tokens):
    """The serving dataflow: prefill a prompt prefix into cache slots,
    decode the rest token-by-token; every step matches the full forward."""
    b, s = tokens.shape
    split = 6
    full = np.asarray(forward(params, tokens, num_heads=HEADS))
    _, k, v = forward_prefill(params, tokens[:, :split], num_heads=HEADS)
    cache = init_cache(
        batch_slots=b, num_layers=CFG["num_layers"], max_seq=16,
        num_heads=HEADS, head_dim=HEAD_DIM,
    )
    for slot in range(b):
        cache = insert_sequence(cache, k[slot], v[slot], slot)
    for t in range(split, s):
        logits, cache = forward_decode(
            params, tokens[:, t], cache, jnp.full((b,), t, jnp.int32),
            num_heads=HEADS,
        )
        np.testing.assert_allclose(np.asarray(logits), full[:, t], atol=1e-5)


def test_cache_bytes_and_shapes():
    cache = init_cache(batch_slots=4, num_layers=2, max_seq=8, num_heads=2,
                       head_dim=4, dtype=jnp.bfloat16)
    assert cache["k"].shape == (4, 2, 8, 2, 4)
    assert cache_bytes(cache) == 2 * 4 * 2 * 8 * 2 * 4 * 2  # k+v, bf16


def test_engine_greedy_matches_oracle(params):
    """Engine-level prefill+decode greedy generation == full-forward
    greedy, with the flash prompt pass (the serving default)."""
    prompt = [5, 17, 3, 42, 8]
    engine = InferenceEngine(
        params, num_heads=HEADS, batch_slots=2, max_seq=24,
        prefill_attention="flash",
    )
    first = engine.prefill(0, prompt)
    got = [first]
    pos = np.array([len(prompt), 0], np.int32)
    toks = np.array([first, 0], np.int32)
    for _ in range(4):
        out = engine.decode(toks, pos)
        got.append(int(out[0]))
        toks[0] = out[0]
        pos[0] += 1
    assert got == _naive_greedy(params, prompt, 5)


# --------------------------------------------------------------------------
# the weights an engine holds: the caller's own, or (where a float32
# product is one bf16 pass: a TPU) their matmul leaves rounded to bf16 once
# --------------------------------------------------------------------------

ENGINES = {
    "paged-pallas": lambda p: _paged(p, decode_kernel="pallas"),
    "paged-gather": lambda p: _paged(p, decode_kernel="gather"),
    "dense": lambda p: InferenceEngine(
        p, num_heads=HEADS, batch_slots=2, max_seq=24),
}

# one full page of 4 shared by all three prompts: the prefix cache hits
SHARED = [7, 7, 9, 2]
PROMPTS = [SHARED + [5, 17, 3], SHARED + [42], SHARED + [8, 8, 30, 1, 6]]


def _paged(params, **kw):
    return PagedInferenceEngine(
        params, num_heads=HEADS, batch_slots=2, max_seq=24, page_size=4,
        prefill_chunk=4, prefix_cache=True, **kw)


def _serve(engine, n=5):
    results, report = ContinuousBatchingScheduler(
        engine, max_new_tokens=n
    ).run([Request(uid=str(i), prompt=p) for i, p in enumerate(PROMPTS)])
    return {int(r.uid): list(r.tokens) for r in results}, report


@pytest.fixture
def one_bf16_pass(monkeypatch):
    """The engine's predicate as a TPU answers it."""
    from distributeddeeplearning_tpu.serve import engine as engine_mod

    monkeypatch.setattr(
        engine_mod, "_f32_product_is_one_bf16_pass", lambda: True)


def test_bf16_pass_predicate_reads_backend_and_precision(monkeypatch):
    from distributeddeeplearning_tpu.serve import engine as engine_mod

    assert engine_mod._f32_product_is_one_bf16_pass() is False  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engine_mod._f32_product_is_one_bf16_pass() is True
    with jax.default_matmul_precision("highest"):
        assert engine_mod._f32_product_is_one_bf16_pass() is False


@pytest.mark.parametrize("build", ENGINES)
def test_engine_holds_the_callers_arrays_off_tpu(build, params):
    """On the CPU a float32 product is a float32 product: the engine's
    parameters are the very tree it was handed, and it says so."""
    engine = ENGINES[build](params)
    assert engine.params is params
    assert engine.weights_dtype == engine.matmul_dtype == "float32"
    assert engine.weights_bytes == cache_bytes(params)


@pytest.mark.parametrize("build", ENGINES)
def test_engine_serves_the_bf16_operand_copy(build, params, one_bf16_pass):
    """Built from float32 parameters where a float32 product is one bf16
    pass, an engine holds ``bf16_matmul_params`` of them and serves, token
    for token, what ``forward`` gives on that tree (prefix cache on); the
    report says what was handed in and what the matmuls read."""
    held = bf16_matmul_params(params)
    engine = ENGINES[build](params)
    for a, b in zip(jax.tree_util.tree_leaves(engine.params),
                    jax.tree_util.tree_leaves(held)):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()
    assert engine.params["embed"] is params["embed"]
    assert params["head"].dtype == jnp.float32  # the caller's tree stands

    tokens, report = _serve(engine)
    for i, prompt in enumerate(PROMPTS):
        assert tokens[i] == _naive_greedy(held, prompt, 5)
    if build != "dense":
        assert report.prefix_hit_rate > 0
    assert report.weights_dtype == "float32"
    assert report.matmul_dtype == "bfloat16"
    assert report.weights_bytes == cache_bytes(held) < cache_bytes(params)


@pytest.mark.parametrize("build", ENGINES)
def test_reload_of_a_float32_tree_keeps_the_bf16_copy(
    build, params, one_bf16_pass
):
    """The caller reloads the float32 tree it always had: the engine
    rounds it again, serves what a fresh engine on the new weights serves,
    and refuses a tree of another shape or storage as before."""
    new = init_params(jax.random.key(5), **CFG)
    fresh, _ = _serve(ENGINES[build](new))
    engine = ENGINES[build](params)
    before, _ = _serve(engine)
    engine.reload_params(new)
    assert engine.params["head"].dtype == jnp.bfloat16
    assert (np.asarray(engine.params["blocks"]["w_in"]) == np.asarray(
        new["blocks"]["w_in"].astype(jnp.bfloat16))).all()
    after, report = _serve(engine)
    assert after == fresh != before
    assert report.matmul_dtype == "bfloat16"

    wider = init_params(jax.random.key(5), **{**CFG, "d_ff": 128})
    with pytest.raises(ValueError, match="reload_params"):
        engine.reload_params(wider)
    all_bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), new)
    with pytest.raises(ValueError, match="reload_params"):
        engine.reload_params(all_bf16)


def test_engine_validates_inputs(params):
    engine = InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                             max_seq=16)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.prefill(0, [])
    with pytest.raises(ValueError, match="no room"):
        engine.prefill(0, list(range(1, 17)))
    with pytest.raises(ValueError, match="slot"):
        engine.prefill(5, [1, 2])
    with pytest.raises(ValueError, match="max_seq"):
        InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                        max_seq=CFG["max_len"] + 1)
    with pytest.raises(ValueError, match="top_k"):
        InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                        max_seq=16, temperature=1.0, top_k=0)


def test_continuous_batching_slot_release_and_reuse(params):
    """More requests than slots: finished sequences release their slot
    mid-flight, newcomers take it, and EVERY completion still matches the
    full-forward greedy oracle (slot reuse must not leak stale K/V)."""
    rng = np.random.default_rng(1)
    prompts = {
        f"r{i}": rng.integers(1, CFG["vocab_size"], rng.integers(2, 9)).tolist()
        for i in range(7)
    }
    engine = InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                             max_seq=24, prefill_attention="dense")
    sched = ContinuousBatchingScheduler(engine, max_new_tokens=4)
    results, report = sched.run(
        [Request(uid=uid, prompt=p) for uid, p in prompts.items()]
    )
    assert len(results) == 7
    for r in results:
        assert r.finish_reason == "length"
        assert r.tokens == _naive_greedy(params, prompts[r.uid], 4), r.uid
        assert r.ttft_s >= 0
    assert report.generated_tokens == 7 * 4
    assert report.requests == 7
    # 7 requests through 2 slots requires >= ceil(7/2)*4 decode... at least
    # more steps than one static batch would take, and occupancy recorded
    assert report.decode_steps >= 4
    assert 0 < report.slot_occupancy_mean <= 1
    assert report.tokens_per_sec > 0
    assert report.ttft_s["p99"] >= report.ttft_s["p50"]


def test_eos_releases_slot_early(params):
    """EOS mid-generation finishes the request with reason 'eos' and frees
    the slot for the queue.  The EOS id is discovered from a dry run so the
    test is robust to the random weights."""
    prompt = [7, 3, 11]
    dry = _naive_greedy(params, prompt, 4)
    eos = dry[1]  # second generated token becomes the EOS id
    engine = InferenceEngine(params, num_heads=HEADS, batch_slots=1,
                             max_seq=16, prefill_attention="dense")
    sched = ContinuousBatchingScheduler(engine, eos_id=eos,
                                        max_new_tokens=8)
    results, report = sched.run(
        [Request(uid="a", prompt=prompt), Request(uid="b", prompt=prompt)]
    )
    assert len(results) == 2
    for r in results:
        assert r.finish_reason == "eos"
        assert r.tokens == dry[:2]  # stops AT the eos token, includes it
    assert report.finish_reasons == {"eos": 2}


def test_per_request_token_budget(params):
    engine = InferenceEngine(params, num_heads=HEADS, batch_slots=2,
                             max_seq=16, prefill_attention="dense")
    sched = ContinuousBatchingScheduler(engine, max_new_tokens=6)
    results, _ = sched.run([
        Request(uid="short", prompt=[4, 9], max_new_tokens=2),
        Request(uid="default", prompt=[4, 9]),
    ])
    by_uid = {r.uid: r for r in results}
    assert len(by_uid["short"].tokens) == 2
    assert len(by_uid["default"].tokens) == 6
    # a zero budget is rejected per-request (not silently promoted to the
    # default, and not raised — in live/fleet mode a raise out of run()
    # would kill the whole worker over one malformed client request)
    results, report = sched.run(
        [Request(uid="zero", prompt=[4, 9], max_new_tokens=0)]
    )
    (res,) = results
    assert res.finish_reason == "error"
    assert "max_new_tokens" in res.error
    assert report.errors == 1


def test_sharded_cache_smoke(params):
    """2-virtual-device mesh: slots shard over the data axes, the run
    completes, and greedy outputs equal the single-device engine's."""
    rng = np.random.default_rng(2)
    prompts = {
        f"r{i}": rng.integers(1, CFG["vocab_size"], rng.integers(2, 7)).tolist()
        for i in range(6)
    }
    requests = [Request(uid=uid, prompt=p) for uid, p in prompts.items()]
    mesh = create_mesh(MeshSpec(), devices=jax.devices()[:2])
    engine = InferenceEngine(params, num_heads=HEADS, batch_slots=4,
                             max_seq=24, mesh=mesh,
                             prefill_attention="dense")
    spec = engine.cache["k"].sharding.spec
    assert spec[0] == ("data", "fsdp")  # slot axis over the data axes
    results, report = ContinuousBatchingScheduler(
        engine, max_new_tokens=3
    ).run(requests)
    assert len(results) == 6
    for r in results:
        assert r.tokens == _naive_greedy(params, prompts[r.uid], 3), r.uid
    # the cache stayed sharded through donated decode steps
    assert engine.cache["k"].sharding.spec[0] == ("data", "fsdp")
    assert report.slot_occupancy_mean > 0

    with pytest.raises(ValueError, match="not divisible"):
        InferenceEngine(params, num_heads=HEADS, batch_slots=3, max_seq=16,
                        mesh=mesh)


def test_top_k_mask_keeps_exactly_k_under_ties():
    """Tie-heavy regression: with many logits equal to the k-th value, a
    threshold mask (`logits < kth`) lets every tied candidate through and
    samples from more than k; the exact-k mask must only ever emit the k
    deterministically-chosen (lowest-index) winners."""
    from distributeddeeplearning_tpu.serve.engine import sample_logits

    vocab = 32
    logits = np.zeros((1, vocab), np.float32)  # ALL tied at the top
    logits[0, 7] = 1.0  # one clear winner + 31 tied at 0.0
    k = 4
    seen = set()
    for step in range(200):
        tok = sample_logits(
            jnp.asarray(logits), jax.random.key(step),
            temperature=1.0, top_k=k,
        )
        seen.add(int(tok[0]))
    # winners are index 7 plus the first k-1 tied indices (0, 1, 2) —
    # lax.top_k breaks ties lowest-index-first
    assert seen <= {7, 0, 1, 2}, f"sampled outside the exact top-{k}: {seen}"
    assert len(seen) > 1  # the draw really is stochastic across steps

    # batched shape: the mask must be per-row, not global
    two = np.stack([logits[0], np.roll(logits[0], 16)])
    toks = sample_logits(
        jnp.asarray(two), jax.random.key(0), temperature=1.0, top_k=1
    )
    assert toks.tolist() == [7, 23]  # top-1 == per-row argmax


def test_temperature_sampling_reproducible(params):
    """Step-folded RNG: same seed -> same stochastic sample stream; a
    different seed decorrelates (train/step.py convention)."""
    def run(seed):
        engine = InferenceEngine(
            params, num_heads=HEADS, batch_slots=1, max_seq=16,
            temperature=1.5, rng=jax.random.key(seed),
            prefill_attention="dense",
        )
        results, _ = ContinuousBatchingScheduler(
            engine, max_new_tokens=6
        ).run([Request(uid="x", prompt=[3, 1, 4])])
        return results[0].tokens

    a, b, c = run(7), run(7), run(8)
    assert a == b
    assert a != c  # 61-way categorical over 6 draws: collision ~impossible


def test_checkpoint_restore_params_roundtrip(params, tmp_path):
    """serve's checkpoint loading: restore_params returns the params
    subtree without needing an optimizer/TrainState template."""
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer

    state = types.SimpleNamespace(
        step=jnp.zeros((), jnp.int32), params=params, opt_state={},
        batch_stats={},
    )
    ckpt = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
    assert ckpt.save(0, state)
    ckpt.wait()
    ckpt.close()
    # restore through a FRESH manager — the serve flow runs in a process
    # that never saved (a same-instance restore hides missing handler args)
    fresh = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
    restored, step = fresh.restore_params()
    fresh.close()
    assert step == 0
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        params, restored,
    )

    empty = Checkpointer(str(tmp_path / "none"), async_save=False)
    assert empty.restore_params() == (None, None)
    empty.close()


def test_cli_serve_synthetic(tmp_path, capsys):
    """ddlt serve --synthetic: continuous-batching run (requests > slots)
    on the virtual pod, SERVE artifact written with the full schema."""
    from distributeddeeplearning_tpu.cli.main import main

    report_path = tmp_path / "SERVE_test.json"
    rc = main([
        "serve", "--synthetic", "--requests", "5", "--batch-slots", "2",
        "--max-new-tokens", "3", "--prompt-len", "6",
        "--num-layers", "2", "--d-model", "32", "--num-heads", "4",
        "--d-ff", "64", "--vocab-size", "61",
        "--prefill-attention", "dense", "--report", str(report_path),
    ])
    assert rc == 0
    stats = json.loads(report_path.read_text())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == stats
    assert stats["requests"] == 5
    assert stats["batch_slots"] == 2
    assert stats["generated_tokens"] == 15
    assert stats["tokens_per_sec"] > 0
    assert {"p50", "p99", "mean", "max"} <= set(stats["ttft_s"])
    assert {"p50", "p99"} <= set(stats["decode_step_s"])
    assert 0 < stats["slot_occupancy_mean"] <= 1
    assert stats["platform"] == "cpu"
    assert stats["virtual_pod"] is True  # conftest forces the 8-CPU pod


def test_cli_serve_prompt_file(tmp_path, capsys):
    """Token-id prompt lines in, uid<TAB>completion lines out."""
    from distributeddeeplearning_tpu.cli.main import main

    pf = tmp_path / "prompts.txt"
    pf.write_text("5 17 3\n# comment\n\n9 2\n")
    rc = main([
        "serve", "--prompt-file", str(pf), "--batch-slots", "2",
        "--max-new-tokens", "2", "--num-layers", "2", "--d-model", "32",
        "--num-heads", "4", "--d-ff", "64", "--vocab-size", "61",
        "--prefill-attention", "dense",
    ])
    assert rc == 0
    out_lines = capsys.readouterr().out.strip().splitlines()
    got = dict(line.split("\t") for line in out_lines)
    assert set(got) == {"line1", "line4"}
    for toks in got.values():
        assert len(toks.split()) == 2


def test_cli_serve_rejects_too_long_prompt(tmp_path, capsys):
    """A prompt that cannot fit the cache fails loudly BEFORE the run —
    an engine error mid-run would discard finished completions."""
    from distributeddeeplearning_tpu.cli.main import main

    pf = tmp_path / "prompts.txt"
    pf.write_text(" ".join(["3"] * 12) + "\n")
    rc = main([
        "serve", "--prompt-file", str(pf), "--max-seq", "8",
        "--num-layers", "2", "--d-model", "32", "--num-heads", "4",
        "--d-ff", "64", "--vocab-size", "61",
    ])
    assert rc == 1
    assert "no room to generate" in capsys.readouterr().err


def test_cli_serve_checkpoint_requires_explicit_heads(tmp_path, capsys):
    """--checkpoint-dir without --num-heads must refuse: a wrong-but-
    dividing default head count would decode garbage silently."""
    from distributeddeeplearning_tpu.cli.main import main

    rc = main([
        "serve", "--synthetic", "--requests", "2",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert rc == 1
    assert "--num-heads" in capsys.readouterr().err


def test_cli_serve_rejects_zero_requests(capsys):
    from distributeddeeplearning_tpu.cli.main import main

    assert main(["serve", "--synthetic", "--requests", "0"]) == 1
    assert "--requests" in capsys.readouterr().err


def test_cli_serve_rejects_out_of_vocab_prompt(tmp_path, capsys):
    """Out-of-range token ids would be clamped silently by jit's gather
    and decode a plausible completion from a wrong prompt — refuse."""
    from distributeddeeplearning_tpu.cli.main import main

    pf = tmp_path / "prompts.txt"
    pf.write_text("99999 5\n")
    rc = main([
        "serve", "--prompt-file", str(pf), "--num-layers", "2",
        "--d-model", "32", "--num-heads", "4", "--d-ff", "64",
        "--vocab-size", "61",
    ])
    assert rc == 1
    assert "outside the model vocab" in capsys.readouterr().err


def test_bench_serve_conflicts_with_devices():
    import subprocess

    proc = subprocess.run(
        [sys.executable, "bench.py", "--serve", "--devices", "1,2"],
        capture_output=True, text=True,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert proc.returncode == 2
    assert "mutually exclusive" in proc.stderr


def test_cli_serve_dry_run(capsys):
    from distributeddeeplearning_tpu.cli.main import main

    assert main(["serve", "--synthetic", "--requests", "9", "--dry-run"]) == 0
    assert "9 request(s)" in capsys.readouterr().out


def test_bench_serve_mode():
    """bench.py --serve emits the SERVE artifact line with provenance."""
    import bench

    args = types.SimpleNamespace(
        small=True, seq_len=8, batch_slots=2, serve_requests=5,
        max_new_tokens=3, serve_temperature=0.0, attention="default",
        kv_layout="dense", page_size=8, prefill_chunk=8, kv_pages=None,
        steps_cap=None, report=None,
    )
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench._run_serve(args)
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["metric"] == "lm_serve_default_tok_sec"
    assert line["unit"] == "tok/sec"
    assert line["value"] > 0
    assert line["requests"] == 5
    assert line["generated_tokens"] == 15
    # the README-documented ServeReport schema (same as ddlt serve
    # --report) plus the ms-denominated conveniences
    assert {"p50", "p99", "mean", "max"} <= set(line["ttft_s"])
    assert line["finish_reasons"] == {"length": 5}
    assert line["wall_s"] > 0
    assert {"p50", "p99"} <= set(line["ttft_ms"])
    assert {"p50", "p99"} <= set(line["decode_step_ms"])
    assert 0 < line["slot_occupancy_mean"] <= 1
    assert line["platform"] == "cpu"
    assert line["virtual_pod"] is True
    assert line["kv_cache_mb"] > 0
    # satellites: queue wait has its own percentile block, and warmup
    # drove every prefill bucket compile out of the benchmarked phase
    assert {"p50", "p99", "mean", "max"} <= set(line["queue_wait_s"])
    assert line["prefill_compiles"] == 0
    assert line["kv_layout"] == "dense"
    assert line["kv_bytes_peak"] == line["kv_bytes"] > 0  # dense: all reserved
