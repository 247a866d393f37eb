"""Tensor-parallel serving: TP=2 vs TP=1 on the suite's virtual pod.

The load-bearing guarantee mirrors the dense-vs-paged suite's: sharding
the serve-path weights over the ``tensor`` axis is a LAYOUT change, never
a math change.  Greedy decode through ``tensor_parallel_engine`` must
produce the SAME tokens as the single-device engine on both KV layouts
and both cache dtypes (the margin-profiled tied-embedding params make the
argmax invariant to the all-reduce's f32 reassociation), chunked-prefill
prefix reuse must survive the sharded page pool, the ServeReport must
carry the TP degree + rule-table provenance into every artifact, and the
TP decode program's per-block all-reduces must classify under
``tp-all-reduce`` — visible to the bench gate, invisible to the gradient
all-reduce count the comm-path lint audits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward,
    init_params,
)
from distributeddeeplearning_tpu.parallel import MeshSpec, comms, create_mesh
from distributeddeeplearning_tpu.parallel.compat import shard_map
from distributeddeeplearning_tpu.parallel.sharding import (
    layout_rules_provenance,
)
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    Request,
)
from distributeddeeplearning_tpu.serve.engine import tensor_parallel_engine

# TP-divisible tiny geometry: heads, d_model, d_ff and vocab all split
# over tensor=2 (an odd vocab would divisibility-drop the head rule and
# the test would silently measure less sharding than it claims)
CFG = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64, vocab_size=64,
           max_len=48)
HEADS = CFG["num_heads"]
MAX_SEQ = 32


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.key(0), **CFG)
    # trained-model margin profile (the bench --tp recipe): tied 4x-gain
    # embedding head so top-2 logit gaps dwarf all-reduce reassociation
    # noise and token equality measures the layout, not tie-breaking
    p["embed"] = p["embed"] * 4.0
    p["head"] = p["embed"].T
    return p


def _build(params, tp, kv_layout, cache_dtype):
    kw = dict(
        tp=tp, num_heads=HEADS, batch_slots=2, max_seq=MAX_SEQ,
        temperature=0.0,
    )
    if cache_dtype is not None:
        kw["cache_dtype"] = cache_dtype
    if kv_layout == "paged":
        kw.update(kv_layout="paged", page_size=4, prefill_chunk=8)
    engine, mesh = tensor_parallel_engine(params, **kw)
    return engine, mesh


def _requests():
    rng = np.random.default_rng(7)
    return [
        Request(
            uid=f"r{i}",
            prompt=rng.integers(
                1, CFG["vocab_size"], 4 + 2 * (i % 3)
            ).tolist(),
        )
        for i in range(4)
    ]


def _naive_greedy(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([toks], jnp.int32),
                         num_heads=HEADS)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


@pytest.mark.parametrize(
    "kv_layout,cache_dtype",
    [
        ("dense", None),
        ("dense", jnp.int8),
        ("paged", None),
        ("paged", jnp.int8),
    ],
    ids=["dense_f32", "dense_int8", "paged_f32", "paged_int8"],
)
def test_tp2_greedy_bit_identical(params, kv_layout, cache_dtype):
    """TP=2 greedy streams equal TP=1 token-for-token on every layout x
    cache dtype — and the f32 configs also match the full-forward oracle
    (int8 quantizes the cache, so its anchor is the TP=1 run alone)."""
    maps = {}
    for tp in (1, 2):
        engine, mesh = _build(params, tp, kv_layout, cache_dtype)
        assert (mesh is None) == (tp == 1)
        res, rep = ContinuousBatchingScheduler(
            engine, max_new_tokens=4
        ).run(_requests())
        maps[tp] = {r.uid: r.tokens for r in res}
        assert rep.tp == tp
    assert maps[1] == maps[2], f"TP=2 diverged on {kv_layout}/{cache_dtype}"
    if cache_dtype is None:
        # one-request oracle anchor: TP=1 == oracle is already pinned
        # exhaustively by the dense/paged suites, so this only guards
        # against BOTH engines sharing a wrong compiled program here
        req = _requests()[0]
        assert maps[2][req.uid] == _naive_greedy(params, req.prompt, 4)


def test_tp2_chunked_prefill_prefix_hits_preserved(params):
    """Shared system-prompt traffic through the TP=2 paged engine: later
    requests still map the shared full pages (nonzero hit rate over the
    SHARDED page pool) and the streams stay equal to TP=1."""
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, CFG["vocab_size"], 12).tolist()
    prompts = {
        f"s{i}": prefix + rng.integers(1, CFG["vocab_size"], 4).tolist()
        for i in range(4)
    }

    def reqs():
        return [Request(uid=u, prompt=p) for u, p in prompts.items()]

    maps, hits = {}, {}
    for tp in (1, 2):
        engine, _ = _build(params, tp, "paged", None)
        res, rep = ContinuousBatchingScheduler(
            engine, max_new_tokens=3
        ).run(reqs())
        maps[tp] = {r.uid: r.tokens for r in res}
        hits[tp] = rep.prefix_hit_rate
        engine.allocator.check()
    assert maps[1] == maps[2]
    assert hits[2] > 0, "prefix reuse vanished under TP"
    assert hits[2] == hits[1], "TP changed WHAT is shareable"


def test_serve_report_carries_tp_and_layout_provenance(params):
    """The satellite provenance contract: every ServeReport (hence every
    SERVE_*/QUANT_*/TP_* artifact line) names its TP degree and the rule
    table that resolved the layout."""
    for tp in (1, 2):
        engine, _ = _build(params, tp, "dense", None)
        _, rep = ContinuousBatchingScheduler(
            engine, max_new_tokens=2
        ).run(_requests()[:2])
        assert rep.tp == tp
        assert rep.layout_rules == layout_rules_provenance()
        line = rep.to_dict()
        assert line["tp"] == tp and line["layout_rules"]


def test_tp2_decode_program_all_reduces_classify_as_tp(params):
    """The compiled TP=2 decode program carries >= 1 per-block all-reduce
    and ``collective_stats(mesh=...)`` files ALL of them under
    ``tp-all-reduce`` — a plain all-reduce residue here would leak into
    the gradient-sync count the comm-path lint audits."""
    engine, mesh = _build(params, 2, "dense", None)
    ContinuousBatchingScheduler(engine, max_new_tokens=2).run(
        _requests()[:2]
    )
    prog = engine._decode_jit
    sig_args, sig_kwargs = list(prog._sigs.values())[-1]
    hlo = prog._fn.lower(*sig_args, **sig_kwargs).compile().as_text()
    stats = comms.collective_stats(hlo, mesh=mesh)
    assert stats.get(comms.TP_ALL_REDUCE, {}).get("count", 0) >= 1, stats
    assert stats.get("all-reduce", {}).get("count", 0) == 0, stats
    # meshless parse: the same traffic reads as plain all-reduce (the
    # classification is the mesh's replica-group knowledge, not a rename)
    flat = comms.collective_stats(hlo)
    assert flat.get("all-reduce", {}).get("count", 0) >= 1, flat


def test_collective_stats_splits_tp_from_data_all_reduce():
    """Unit pin for the classifier: on a data=2 x tensor=2 mesh, a psum
    over ``tensor`` classifies as tp-all-reduce while a psum over the
    data axes stays a plain all-reduce."""
    mesh = create_mesh(
        MeshSpec(data=2, tensor=2), devices=jax.devices()[:4]
    )

    def f(x):
        # two DISTINCT live outputs — a nested psum would let XLA fuse
        # both reductions into one whole-mesh collective
        return jax.lax.psum(x, "tensor"), jax.lax.psum(x, "data")

    fn = shard_map(
        f, mesh=mesh, in_specs=P(("data", "tensor")),
        out_specs=(P("data"), P("tensor")),
    )
    hlo = jax.jit(fn).lower(jnp.ones(8, jnp.float32)).compile().as_text()
    stats = comms.collective_stats(hlo, mesh=mesh)
    assert stats.get(comms.TP_ALL_REDUCE, {}).get("count", 0) >= 1, stats
    assert stats.get("all-reduce", {}).get("count", 0) >= 1, stats


@pytest.mark.parametrize("cache_dtype", [None, jnp.int8], ids=["f32", "int8"])
def test_folded_pool_shards_whole_heads_and_same_logits(params, cache_dtype):
    """The paged pool folds its heads into the minor axis: the ``kv_paged``
    rule's tensor shard of that axis is a contiguous group of WHOLE heads
    (the heads its scales' shard names), the ``attn/*_pages`` rule hands
    each chip's kernel instance those local heads, and a decode step
    through the per-shard Pallas kernel gives the logits of the one-device
    program."""
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward_decode_paged,
        forward_prefill,
    )
    from distributeddeeplearning_tpu.ops import flash_decode as fd
    from distributeddeeplearning_tpu.parallel import sharding as layout
    from distributeddeeplearning_tpu.serve import (
        init_paged_cache,
        insert_pages,
    )
    from distributeddeeplearning_tpu.serve.kv_cache import cache_sharding

    hd, page, tp = CFG["d_model"] // HEADS, 4, 2
    mesh = create_mesh(MeshSpec(data=1, tensor=tp), devices=jax.devices()[:tp])
    tokens = jnp.asarray([[5, 17, 3, 42, 8, 9, 11, 2]], jnp.int32)
    _, k, v = forward_prefill(params, tokens, num_heads=HEADS)
    kw = {} if cache_dtype is None else {"dtype": cache_dtype}
    cache = insert_pages(
        init_paged_cache(num_pages=6, num_layers=CFG["num_layers"],
                         page_size=page, num_heads=HEADS, head_dim=hd, **kw),
        k[0], v[0], jnp.asarray([2, 5], jnp.int32), page_size=page,
    )
    shardings = cache_sharding(
        mesh, quantized=cache_dtype is not None, layout="paged")
    placed = jax.device_put(cache, shardings)
    local = HEADS // tp
    for name, leaf in placed.items():
        assert shardings[name].spec == P(None, None, None, "tensor"), name
        width = local * (1 if name.endswith("_scale") else hd)
        for i, shard in enumerate(
                sorted(leaf.addressable_shards, key=lambda s: s.device.id)):
            assert shard.data.shape == leaf.shape[:-1] + (width,), name
            np.testing.assert_array_equal(  # heads [i*local, (i+1)*local)
                np.asarray(shard.data),
                np.asarray(cache[name])[..., i * width:(i + 1) * width])

    # the kernel's operands under the mesh: pages and scales split their
    # minor axis, q and out their head axis, all to the same local heads
    q4 = jnp.zeros((2, 1, HEADS, hd), jnp.float32)
    rows = placed["k"].reshape((-1,) + placed["k"].shape[2:])
    names, in_specs, out_spec = fd.attention_partition_specs(
        {"q": q4, "k_pages": rows, "v_pages": rows,
         "k_scale": None, "v_scale": None}, mesh=mesh)
    assert dict(zip(names, in_specs)) == {
        "q": P(None, None, "tensor"),  # [b, nq, h, hd]: the head axis
        "k_pages": P(None, None, "tensor"),  # [rows, page, h * hd]: the minor
        "v_pages": P(None, None, "tensor"),
    }
    assert out_spec == P(None, None, "tensor")

    token = jnp.asarray([7, 9], jnp.int32)
    pos = jnp.asarray([8, 3], jnp.int32)  # slot 0 appends, slot 1 mid-page
    tables = jnp.asarray([[2, 5, 1], [3, 0, 0]], jnp.int32)

    def step(p, c, *, mesh):
        return forward_decode_paged(
            p, token, c, pos, tables, num_heads=HEADS, page_size=page,
            kernel="pallas", mesh=mesh)

    want, want_cache = jax.jit(functools.partial(step, mesh=None))(
        params, cache)
    sharded_params = jax.device_put(
        params, layout.resolve_shardings(mesh, params, prefix="params"))
    per_shard = functools.partial(step, mesh=mesh)
    assert "shard_map" in str(jax.make_jaxpr(per_shard)(sharded_params, placed))
    got, got_cache = jax.jit(per_shard)(sharded_params, placed)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    for name in cache:
        assert got_cache[name].shape == cache[name].shape
        np.testing.assert_allclose(
            np.asarray(got_cache[name]).astype(np.float32),
            np.asarray(want_cache[name]).astype(np.float32),
            atol=1e-6, err_msg=name)
