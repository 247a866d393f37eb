"""The `mimo_v2` family: a decoder that mixes window and full attention layers
(each kind with its own KV head count, rotary base and cache lifetime) and
routes its FFN over sparse experts of which this chip holds a stated subset,
as this repo's program runs it (`models/hybrid_moe_transformer.py` behind
`serve/served_model.hybrid_model`). Served only: it gives no `build_train`.

The contract is `families/opt.py`'s docstring. Beside this module: its
weights (`mimo_v2_weights.py`), its plain reference (`mimo_v2_reference.py`,
which imports nothing of the program) and its counts (`mimo_v2_flops.py`).

A configuration's file keeps the published keys whole (the 48-entry layer
patterns included) and says which of the published layers are run
(`layers_kept`); `run_config` cuts the patterns to those, and everything
here, the reference included, is handed the cut configuration.
"""
import importlib.util

import mimo_v2_flops as counts
import mimo_v2_reference as reference
import mimo_v2_weights as weights

#: the family's programs as the `XLA Modules` line of a trace names them
PROGRAMS = {
    "decode": "jit__hybrid_decode_fn",
    "prefill_chunk": "jit__hybrid_chunk_fn",
}

if importlib.util.find_spec(
        "distributeddeeplearning_tpu.models.hybrid_moe_transformer") is None:
    # a tree from before the program could run this family: stop here, before
    # any device work, with a message and a non-zero exit
    raise SystemExit("family 'mimo_v2': this tree's program has no "
                     "models/hybrid_moe_transformer.py, so it cannot run it")


def run_config(cfg: dict) -> dict:
    """The configuration as it is run: the layer patterns cut to the
    published layers the file keeps."""
    kept = cfg.get("layers_kept")
    if kept is None:
        return cfg
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError("layers_kept and num_hidden_layers disagree")
    out = dict(cfg)
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        out[key] = [cfg[key][i] for i in kept]
    out.pop("layers_kept")
    return out


def make_params(seed, cfg):
    return weights.make_params(seed, run_config(cfg))


def param_shapes(cfg, sharding=None):
    return weights.param_shapes(run_config(cfg), sharding)


def matmul_params(cfg):
    return counts.matmul_params(run_config(cfg))


def serve_token_flops(cfg, context):
    return counts.serve_token_flops(run_config(cfg), context)


def decode_step_bytes(cfg, contexts, experts_touched):
    return counts.decode_step_bytes(run_config(cfg), contexts, experts_touched)


def gqa_decode_call(cfg, contexts):
    return counts.gqa_decode_call(run_config(cfg), contexts)


def full_layers(cfg) -> int:
    return sum(1 for k in run_config(cfg)["hybrid_layer_pattern"] if k == 0)


def served_model(cfg):
    from distributeddeeplearning_tpu.models.hybrid_moe_transformer import (
        spec_from_config)
    from distributeddeeplearning_tpu.serve.served_model import hybrid_model

    return hybrid_model(spec_from_config(run_config(cfg)))


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


def served_token_gaps(params, tokens, cfg, precision="float32"):
    return reference.served_token_gaps(params, tokens, run_config(cfg),
                                       precision=precision)


def aot_serve_programs(cfg, kv_pages, sharding):
    """The decode step and one full prefill chunk over a pool of `kv_pages`
    and the window layers' rings, as functions with the shapes to lower them
    at (the cache is argument 1 and is donated), and the cache's bytes."""
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
