"""The `jamba` family: a decoder of state-space (Mamba) layers and, one in
`attn_layer_period`, attention layers with one KV head and no position signal,
every layer with a dense gated FFN and the head tied to the embedding, as this
repo's program runs it (`models/hybrid_moe_transformer.py` behind
`serve/served_model.hybrid_model`, the chunk scan in `ops/ssm_scan.py`). Served
only: it gives no `build_train`.

The contract is `families/opt.py`'s docstring. Beside this module: its
weights (`jamba_weights.py`), its plain reference (`jamba_reference.py`, which
imports nothing of the program) and its counts (`jamba_flops.py`).

A configuration's file keeps the published keys whole; the program's
`spec_from_config`, the weights, the counts and the reference each read that
file as it is.
"""
import jamba_flops as counts
import jamba_reference as reference
import jamba_weights as weights

#: the family's programs as the `XLA Modules` line of a trace names them
PROGRAMS = {
    "decode": "jit__hybrid_decode_fn",
    "prefill_chunk": "jit__hybrid_chunk_fn",
}

#: what the family needs of the program's model module and its `HybridSpec`
NEEDS_OF_MODULE = ("MAMBA", "mamba_mixer")
NEEDS_OF_SPEC = ("ssm",)


def _program():
    """The program's model module, or a stop, before any device work, with a
    message and a non-zero exit on a tree whose program cannot run this
    family (no module, or one with no state-space layer kind)."""
    import importlib

    try:
        module = importlib.import_module(
            "distributeddeeplearning_tpu.models.hybrid_moe_transformer")
    except ImportError:
        module = None
    have = getattr(getattr(module, "HybridSpec", None), "__dataclass_fields__", {})
    lacks = ([f"{name}" for name in NEEDS_OF_MODULE if not hasattr(module, name)]
             + [f"HybridSpec.{f}" for f in NEEDS_OF_SPEC if f not in have])
    if lacks:
        raise SystemExit("family 'jamba': this tree's program has no "
                         f"{', '.join(lacks)} in models/hybrid_moe_transformer.py "
                         "(no state-space layer kind), so it cannot run it")
    return module


_program()

make_params = weights.make_params
param_shapes = weights.param_shapes
matmul_params = counts.matmul_params
serve_token_flops = counts.serve_token_flops
decode_step_bytes = counts.decode_step_bytes
gqa_decode_call = counts.gqa_decode_call
full_layers = counts.full_layers
ssm_layers = counts.ssm_layers
ssm_scan_call = counts.ssm_scan_call
served_token_gaps = reference.served_token_gaps


def served_model(cfg):
    from distributeddeeplearning_tpu.serve.served_model import hybrid_model

    return hybrid_model(_program().spec_from_config(cfg))


def build_serve(cfg, params):
    from distributeddeeplearning_tpu.serve.engine import PagedInferenceEngine
    from distributeddeeplearning_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    geo = cfg["serving"]
    engine = PagedInferenceEngine(
        params,
        model=served_model(cfg),
        batch_slots=geo["batch_slots"],
        max_seq=geo["max_seq"],
        page_size=geo["page_size"],
        num_pages=geo["kv_pages"],
        prefill_chunk=geo["prefill_chunk"],
        decode_kernel=geo["decode_kernel"],
        prefix_cache=geo["prefix_cache"],
    )
    return engine, ContinuousBatchingScheduler(engine, eos_id=None)


def aot_serve_programs(cfg, kv_pages, sharding):
    """The decode step and one full prefill chunk over a pool of `kv_pages`
    and the slots' states, as functions with the shapes to lower them at (the
    cache is argument 1 and is donated), and the cache's bytes."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.serve import kv_cache

    geo = cfg["serving"]
    model = served_model(cfg)
    params = param_shapes(cfg, sharding)
    ps, slots = geo["page_size"], geo["batch_slots"]
    nb = -(-geo["max_seq"] // ps)
    cache = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(lambda: model.init_cache(
            num_pages=kv_pages, page_size=ps, batch_slots=slots,
            dtype=jnp.bfloat16)))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def decode(p, c, tok, pos, tables, live):
        logits, c, counted = model.decode(
            p, tok, c, pos, tables, live, page_size=ps, kernel="pallas")
        return jnp.argmax(logits, -1), jnp.isfinite(logits).all(-1), counted, c

    def chunk(p, c, toks, table, off, slot, real):
        return model.prefill_chunk(p, toks, c, table, off, slot, real,
                                   page_size=ps, kernel="pallas")

    i32 = jnp.int32
    programs = {
        "decode": (decode, (params, cache, arr(i32, slots), arr(i32, slots),
                            arr(i32, slots, nb), arr(jnp.bool_, slots))),
        "prefill_chunk": (chunk, (params, cache, arr(i32, 1, geo["prefill_chunk"]),
                                  arr(i32, nb), arr(i32), arr(i32), arr(i32))),
    }
    return programs, kv_cache.cache_bytes(cache)
