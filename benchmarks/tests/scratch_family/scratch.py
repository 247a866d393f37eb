"""A second family, for `test_family_only.py` alone: it arrives in a scratch
copy of the benchmark as new files and entries, and no file that is there is
edited. The program has one block, so it is served by the same engine; it
differs from `opt` wherever the harness could have bound `opt` by mistake: a
weight maker of its own (another initializer range, another key for every
leaf), a reference file of its own (`scratch_reference.py`, a copy of the
mathematics and no import of it), counts no other family has, and no train
side at all."""
import functools

import jax
import jax.numpy as jnp
import scratch_reference

INIT_STD = 0.05
PROGRAMS = {"decode": "jit__decode_fn", "prefill_chunk": "jit__chunk_fn"}


@functools.partial(jax.jit, static_argnames=("L", "d", "ff", "vocab", "max_len"))
def _make(key, *, L, d, ff, vocab, max_len):
    def nrm(name, shape):
        k = jax.random.fold_in(key, sum(name.encode()))
        return jax.random.normal(k, shape, jnp.float32) * INIT_STD

    return {
        "head": nrm("head", (d, vocab)),
        "blocks": {
            "w_out": nrm("w_out", (L, ff, d)),
            "w_in": nrm("w_in", (L, d, ff)),
            "proj": nrm("proj", (L, d, d)),
            "qkv": nrm("qkv", (L, d, 3 * d)),
            "ln2": jnp.ones((L, d), jnp.float32),
            "ln1": jnp.ones((L, d), jnp.float32),
        },
        "pos": nrm("pos", (max_len, d)),
        "embed": nrm("embed", (vocab, d)),
    }


def make_params(seed, cfg):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed % (2**31 - 1)), seed >> 31)
    return _make(key, L=cfg["layers"], d=cfg["width"], ff=cfg["inner_width"],
                 vocab=cfg["vocab_size"], max_len=cfg["positions"])


def build_serve(cfg, params):
    from distributeddeeplearning_tpu.serve.engine import PagedInferenceEngine
    from distributeddeeplearning_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    geo = cfg["serving"]
    engine = PagedInferenceEngine(
        params, num_heads=cfg["heads"], batch_slots=geo["batch_slots"],
        max_seq=geo["max_seq"], page_size=geo["page_size"],
        num_pages=geo["kv_pages"], prefill_chunk=geo["prefill_chunk"],
        decode_kernel=geo["decode_kernel"], prefix_cache=geo["prefix_cache"])
    return engine, ContinuousBatchingScheduler(engine, eos_id=None)


def served_token_gaps(params, tokens, cfg, precision="float32"):
    return scratch_reference.served_token_gaps(
        params, tokens, num_heads=cfg["heads"], precision=precision)


def matmul_params(cfg):
    """A count that no configuration of `opt` has: the file states it."""
    return cfg["counted_matmul_params"]


def serve_token_flops(cfg, context):
    return 2.0 * matmul_params(cfg) + 4 * cfg["layers"] * cfg["width"] * context
