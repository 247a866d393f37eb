"""Seeded weights of the `afmoe` family, made on the device leaf by leaf.

The pytree is the one `models/hybrid_moe_transformer.py` documents for an
untied head: `embed`, `final_norm`, `head`, and under `layers` one dict a
layer: `ln1`, the attention's `wq`, `wk`, `wv`, `wo`, `q_norm`, `k_norm` and
its output gate `w_gate`; `ln2`; the two norms after the operators,
`ln1_post` and `ln2_post`; then the dense FFN's `wg`, `wu`, `wd` or an expert
layer's `router`, `router_bias`, `wg`, `wu`, `wd` with the held experts
leading and the always-on expert's `shared_wg`, `shared_wu`, `shared_wd`. The
program and the plain reference are handed the same arrays. Every matrix and
the router's correction bias is normal(0, 0.02) (a non-zero bias, so that
selecting by s + b and weighing by s differ; a configuration may state another
`init_std`; only the rehearsals' tiny sizes do, to have logits apart); norm
scales are 1. The shapes come from the configuration's published keys alone,
so this file imports nothing of the program."""
import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
NORM_SCALES = ("ln1", "ln2", "ln1_post", "ln2_post", "q_norm", "k_norm",
               "final_norm")


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def layers_of(cfg: dict):
    """(sliding window?, dense FFN?) of every layer that is run: the published
    layers `layers_kept` names, or the first `num_hidden_layers`."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i] == "sliding_attention",
             i < cfg["num_dense_layers"]) for i in kept]


def experts_held(cfg: dict) -> int:
    return len(cfg.get("experts_held", range(cfg["num_experts"])))


def router_width(cfg: dict) -> int:
    return cfg.get("num_experts_published", cfg["num_experts"])


def shared_width(cfg: dict) -> int:
    return cfg["num_shared_experts"] * cfg["moe_intermediate_size"]


def leaf_shapes(cfg: dict) -> dict:
    """path -> shape, in a fixed order (the order the keys are dealt in)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dh, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    out = {("embed",): (cfg["vocab_size"], d), ("final_norm",): (d,),
           ("head",): (d, cfg["vocab_size"])}
    for layer, (_, dense) in enumerate(layers_of(cfg)):
        shapes = {"ln1": (d,), "wq": (d, heads * dh), "wk": (d, kv * dh),
                  "wv": (d, kv * dh), "wo": (heads * dh, d),
                  "q_norm": (dh,), "k_norm": (dh,),
                  "w_gate": (d, heads * dh), "ln1_post": (d,), "ln2": (d,),
                  "ln2_post": (d,)}
        if dense:
            ff = cfg["intermediate_size"]
            shapes.update(wg=(d, ff), wu=(d, ff), wd=(ff, d))
        else:
            fe, held = cfg["moe_intermediate_size"], experts_held(cfg)
            shapes.update(router=(d, router_width(cfg)),
                          router_bias=(router_width(cfg),),
                          wg=(held, d, fe), wu=(held, d, fe), wd=(held, fe, d))
            if shared_width(cfg):
                fs = shared_width(cfg)
                shapes.update(shared_wg=(d, fs), shared_wu=(d, fs),
                              shared_wd=(fs, d))
        for name, shape in shapes.items():
            out[("layers", layer, name)] = shape
    return out


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, *, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _tree(cfg, leaf):
    out = {"layers": [{} for _ in range(cfg["num_hidden_layers"])]}
    for i, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        node = out if path[0] != "layers" else out["layers"][path[1]]
        node[path[-1]] = leaf(i, path[-1], shape)
    return out


def make_params(seed: int, cfg: dict):
    dtype = DTYPES[cfg["storage_dtype"]]
    key = seed_key(seed)
    std = float(cfg.get("init_std", INIT_STD))

    def leaf(i, name, shape):
        if name in NORM_SCALES:
            return jnp.ones(shape, dtype)
        return _normal(jax.random.fold_in(key, i), shape=shape, dtype=dtype,
                       std=std)

    return _tree(cfg, leaf)


def param_shapes(cfg: dict, sharding=None):
    """The same pytree as shapes (for compiling with no device to hold it)."""
    dtype = DTYPES[cfg["storage_dtype"]]
    return _tree(cfg, lambda i, name, shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding))
