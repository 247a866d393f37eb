"""The paged forwards walk the page pool in place.

``models.pipelined_transformer._scan_pool`` carries every pool leaf through
the layer scan as rows ``[(pages+1) * L, page_size, h * hd]`` (layer ``l``
of page ``p`` at row ``p * L + l``; the pool folds its heads into the minor
axis) and writes new positions into that carry.  Pinned here, on the CPU:

- logits and EVERY pool leaf, the scratch page included, are equal to every
  bit to a plain reference written in this file: a Python loop over the
  layers, each on its own slice ``cache[leaf][:, l]`` under the block tables
  as they stand, with no scan and no row view;
- the compiled program's temporaries do not grow with the pool (a pool that
  rides the scan as input and stacked output makes them 1.7 x the pool);
- compiled for a described TPU v5e (no chip), at the served cell's widths,
  the pool's default layout is row-major and the decode and chunk programs
  set aside next to nothing beside it: the row view is a bitcast (a trailing
  ``(32, 64)`` made the page axis minor and cost four whole-pool copies).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models import pipelined_transformer as pt
from distributeddeeplearning_tpu.ops import flash_decode as fd
from distributeddeeplearning_tpu.serve import init_paged_cache

LAYERS, HEADS, HEAD_DIM, PAGE = 3, 4, 8, 4
CFG = dict(num_layers=LAYERS, d_model=HEADS * HEAD_DIM, num_heads=HEADS,
           d_ff=64, vocab_size=61, max_len=64)
SLOTS, NB, PAGES = 3, 4, 14  # a slot addresses NB pages: 16 positions


@pytest.fixture(scope="module")
def params():
    return pt.init_params(jax.random.key(0), **CFG)


def _pool(dtype, seed):
    """A pool with something in every row, the scratch page included."""
    rng = np.random.default_rng(seed)
    rows = (PAGES + 1, LAYERS, PAGE)
    shape = rows + (HEADS * HEAD_DIM,)  # heads folded, as the engines hold it
    if dtype == "int8":
        return {
            "k": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "k_scale": jnp.asarray(rng.uniform(1e-3, 2e-2, rows + (HEADS,)),
                                   jnp.float32),
            "v_scale": jnp.asarray(rng.uniform(1e-3, 2e-2, rows + (HEADS,)),
                                   jnp.float32),
        }
    return {"k": jnp.asarray(rng.normal(size=shape), jnp.float32),
            "v": jnp.asarray(rng.normal(size=shape), jnp.float32)}


def _tables(seed):
    """Distinct pages a slot, in no order; the last slot is released (every
    entry the scratch page), as the engine leaves it."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, PAGES + 1))[:(SLOTS - 1) * NB]
    tables = np.zeros((SLOTS, NB), np.int32)
    tables[:SLOTS - 1] = ids.reshape(SLOTS - 1, NB)
    return jnp.asarray(tables)


def _reference(params, x, cache, pages, offs, attend):
    """Layer by layer in a Python loop: each layer reads and writes its own
    slice ``cache[leaf][:, l]`` at ``[pages, offs]`` and attends over it.
    One layer is one compiled program, as the scan's body is (compiled
    whole, the unrolled loop fuses otherwise and differs in the last bit)."""

    @jax.jit
    def layer(p, x, leaf):
        lead = x.shape[:-1]
        h = pt._layer_norm(x, p["ln1"])
        q, k_c, v_c = (
            t.reshape(lead + (HEADS, HEAD_DIM))
            for t in jnp.split(pt._mm(h, p["qkv"]), 3, axis=-1)
        )
        if "k_scale" in leaf:  # quantized per head, then folded
            kq, ks = pt._q_kv(k_c)
            vq, vs = pt._q_kv(v_c)
            new = {"k": kq.reshape(lead + (-1,)), "v": vq.reshape(lead + (-1,)),
                   "k_scale": ks, "v_scale": vs}
        else:
            new = {"k": k_c.reshape(lead + (-1,)),
                   "v": v_c.reshape(lead + (-1,))}
        leaf = {n: a.at[pages, offs].set(new[n].astype(a.dtype))
                for n, a in leaf.items()}
        ctx = attend(q, k_c, v_c, leaf["k"], leaf["v"],
                     leaf.get("k_scale"), leaf.get("v_scale"))
        x = x + pt._mm(ctx.reshape(lead + (-1,)).astype(x.dtype), p["proj"])
        h = pt._layer_norm(x, p["ln2"])
        x = x + pt._mm(
            jax.nn.gelu(pt._mm(h, p["w_in"]), approximate=False), p["w_out"])
        return x, leaf

    cache = dict(cache)
    for l in range(LAYERS):
        p = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        x, leaf = layer(p, x, {n: a[:, l] for n, a in cache.items()})
        for n in cache:
            cache[n] = cache[n].at[:, l].set(leaf[n])
    return pt._mm(x, params["head"]), cache


def _decode_pair(kernel, seed):
    rng = np.random.default_rng(seed)
    tables = _tables(seed)
    token = jnp.asarray(rng.integers(1, CFG["vocab_size"], SLOTS), jnp.int32)
    pos = jnp.asarray([5, 15, 0], jnp.int32)  # mid-page, last position, idle

    def program(params, cache):
        return pt.forward_decode_paged(
            params, token, cache, pos, tables, num_heads=HEADS,
            page_size=PAGE, kernel=kernel)

    def reference(params, cache):
        x = params["embed"][token] + params["pos"][pos]

        def attend(q, k_t, v_t, k_l, v_l, k_s, v_s):
            return fd.decode_attention_paged(
                q, k_l, v_l, k_s, v_s, k_t, v_t, pos, tables,
                page_size=PAGE, kernel=kernel)

        return _reference(params, x, cache,
                          tables[jnp.arange(SLOTS), pos // PAGE], pos % PAGE,
                          attend)

    return program, reference


def _chunk_pair(kernel, seed, offset):
    rng = np.random.default_rng(seed)
    table = _tables(seed)[0]
    C = 8
    tokens = jnp.asarray(rng.integers(1, CFG["vocab_size"], (1, C)), jnp.int32)

    def program(params, cache):
        return pt.forward_prefill_chunk(
            params, tokens, cache, table, jnp.int32(offset), num_heads=HEADS,
            page_size=PAGE, kernel=kernel)

    def reference(params, cache):
        posns = offset + jnp.arange(C)
        idx = posns // PAGE
        pages = jnp.where(idx < NB, table[jnp.minimum(idx, NB - 1)], 0)
        x = (params["embed"][tokens[0]]
             + params["pos"][jnp.minimum(posns, CFG["max_len"] - 1)])

        def attend(q, k_c, v_c, k_l, v_l, k_s, v_s):
            return fd.chunk_attention(
                q, k_l, v_l, k_s, v_s, table, posns, page_size=PAGE,
                kernel=kernel)

        logits, new = _reference(params, x, cache, pages, posns % PAGE, attend)
        return logits[None], new

    return program, reference


def _verify_pair(kernel, seed):
    rng = np.random.default_rng(seed)
    tables = _tables(seed)
    K1 = 4
    tokens = jnp.asarray(
        rng.integers(1, CFG["vocab_size"], (SLOTS, K1)), jnp.int32)
    # the second slot's drafts run past its block table, the third is idle
    pos = jnp.asarray([5, 14, 0], jnp.int32)
    draft_len = jnp.asarray([3, 2, 0], jnp.int32)

    def program(params, cache):
        return pt.forward_verify_paged(
            params, tokens, cache, pos, draft_len, tables, num_heads=HEADS,
            page_size=PAGE, kernel=kernel)

    def reference(params, cache):
        posmat = pos[:, None] + jnp.arange(K1)[None]
        idx = posmat // PAGE
        ok = (jnp.arange(K1)[None] <= draft_len[:, None]) & (idx < NB)
        rows = jnp.arange(SLOTS)[:, None]
        pages = jnp.where(ok, tables[rows, jnp.minimum(idx, NB - 1)], 0)
        offs = jnp.where(ok, posmat % PAGE, 0)
        x = (params["embed"][tokens]
             + params["pos"][jnp.minimum(posmat, CFG["max_len"] - 1)])

        def attend(q, k_c, v_c, k_l, v_l, k_s, v_s):
            return fd.verify_attention_paged(
                q, k_l, v_l, tables, posmat, page_size=PAGE, kernel=kernel)

        return _reference(params, x, cache, pages, offs, attend)

    return program, reference


CASES = [
    pytest.param(form, pool, kernel, id=f"{form}-{pool}-{kernel}")
    for form in ("decode", "chunk", "chunk_overflow", "verify")
    for pool in ("f32", "int8")
    for kernel in ("gather", "flash")
    if not (form == "verify" and pool == "int8")  # verify refuses int8
]


@pytest.mark.parametrize("form,pool,kernel", CASES)
def test_pool_walked_in_place_equals_layer_loop_bitwise(
        params, form, pool, kernel):
    cache = _pool(pool, seed=3)
    if form == "decode":
        program, reference = _decode_pair(kernel, seed=4)
    elif form == "verify":
        program, reference = _verify_pair(kernel, seed=5)
    else:
        # 16 positions in the table: a chunk of 8 at 12 runs 4 past its end
        offset = 12 if form == "chunk_overflow" else 4
        program, reference = _chunk_pair(kernel, 6, offset)
    logits, new = jax.jit(program)(params, cache)
    want_logits, want = reference(params, cache)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    assert set(new) == set(cache)
    for name in cache:
        assert new[name].shape == cache[name].shape
        assert new[name].dtype == cache[name].dtype
        np.testing.assert_array_equal(
            np.asarray(new[name]), np.asarray(want[name]), err_msg=name)
    # something was written, and (overflow) into the scratch page's layers
    assert not np.array_equal(np.asarray(new["k"]), np.asarray(cache["k"]))
    if form == "chunk_overflow":
        assert not np.array_equal(
            np.asarray(new["k"][0]), np.asarray(cache["k"][0]))


def test_int8_verify_is_refused(params):
    program, _ = _verify_pair("gather", seed=5)
    with pytest.raises(ValueError, match="f32 cache layout only"):
        program(params, _pool("int8", 3))


@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_temporaries_do_not_grow_with_the_pool(params, form):
    """16 pages or 256: the donated pool is updated where it lies, so what
    the compiled program sets aside beside its arguments is the same."""

    def temporaries(num_pages):
        cache = jax.eval_shape(functools.partial(
            init_paged_cache, num_pages=num_pages, num_layers=LAYERS,
            page_size=PAGE, num_heads=HEADS, head_dim=HEAD_DIM))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        if form == "decode":
            fn = lambda p, c, tok, pos, tables: pt.forward_decode_paged(  # noqa: E731
                p, tok, c, pos, tables, num_heads=HEADS, page_size=PAGE)
            args = (i32(SLOTS), i32(SLOTS), i32(SLOTS, NB))
        else:
            fn = lambda p, c, toks, table, off: pt.forward_prefill_chunk(  # noqa: E731
                p, toks, c, table, off, num_heads=HEADS, page_size=PAGE)
            args = (i32(1, 8), i32(NB), i32())
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *args).compile()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
        return compiled.memory_analysis().temp_size_in_bytes, pool_bytes

    small, _ = temporaries(16)
    large, pool_bytes = temporaries(256)
    assert large == small, (small, large)
    assert large < pool_bytes / 4


# -- the same two programs compiled for a described TPU v5e: no chip, shapes
# only.  The topology is described inside a fixture, never at import (one
# process at a time may load the TPU's library; see the module docstring of
# tests/test_chip_lowering.py for what lowering alone can show).

CELL = dict(num_layers=2, d_model=2048, num_heads=32, d_ff=8192,
            vocab_size=50000, max_len=2048)  # galactica-1.3b's widths
CELL_PAGE, CELL_PAGES, CELL_SLOTS, CELL_NB, CELL_CHUNK = 64, 64, 16, 10, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_pool_lies_row_major_on_a_v5e_and_no_program_copies_it(
        one_chip, monkeypatch, form):
    """The guard that no later shape change brings the transposes back."""
    from distributeddeeplearning_tpu.quant import bf16_matmul_params

    monkeypatch.setattr(fd, "_use_interpret", lambda: False)  # Mosaic compiles

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    # what the engine serves from on a TPU: matmul leaves rounded to bf16
    weights = on_chip(jax.eval_shape(
        lambda: bf16_matmul_params(pt.init_params(jax.random.key(0), **CELL))))
    cache = on_chip(jax.eval_shape(functools.partial(
        init_paged_cache, num_pages=CELL_PAGES, num_layers=CELL["num_layers"],
        page_size=CELL_PAGE, num_heads=CELL["num_heads"],
        head_dim=CELL["d_model"] // CELL["num_heads"])))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    kw = dict(num_heads=CELL["num_heads"], page_size=CELL_PAGE, kernel="pallas")
    if form == "decode":
        fn = lambda p, c, tok, pos, tables: pt.forward_decode_paged(  # noqa: E731
            p, tok, c, pos, tables, **kw)
        args = (i32(CELL_SLOTS), i32(CELL_SLOTS), i32(CELL_SLOTS, CELL_NB))
    else:
        fn = lambda p, c, toks, table, off: pt.forward_prefill_chunk(  # noqa: E731
            p, toks, c, table, off, **kw)
        args = (i32(1, CELL_CHUNK), i32(CELL_NB), i32())
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        weights, cache, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, via Mosaic
    pool_formats = compiled.input_formats[0][1]
    for name, leaf in cache.items():
        assert leaf.shape == (CELL_PAGES + 1, CELL["num_layers"], CELL_PAGE,
                              CELL["d_model"])
        assert tuple(pool_formats[name].layout.major_to_minor) == (0, 1, 2, 3), (
            name, pool_formats[name])
    pool_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < pool_bytes / 10, (temporaries, pool_bytes)
