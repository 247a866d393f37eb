"""Seeded weights of the `mimo_v2` family, made on the device leaf by leaf.

The pytree is the one `models/hybrid_moe_transformer.py` documents: `embed`,
`head`, `final_norm`, and under `layers` one dict a layer (`ln1`, `wq`, `wk`,
`wv`, `wo`, `ln2`, a window layer's `sink`, then the dense FFN's `wg`, `wu`,
`wd` or an expert layer's `router`, `router_bias` and `wg`, `wu`, `wd` with the
held experts leading). The program and the plain reference are handed the same
arrays. Every matrix, every sink logit and the router's correction bias is
normal(0, 0.02) (a configuration may state another `init_std`; only the
rehearsals' tiny sizes do, to have logits apart); norm scales are 1. The shapes come from the configuration's
published keys alone, so this file imports nothing of the program."""
import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_shapes(cfg: dict) -> dict:
    """path -> shape, in a fixed order (the order the keys are dealt in)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    held = len(cfg["experts_held"]) if cfg.get("experts_held") is not None \
        else cfg["n_routed_experts"]
    router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    out = {("embed",): (cfg["vocab_size"], d), ("head",): (d, cfg["vocab_size"]),
           ("final_norm",): (d,)}
    for layer in range(cfg["num_hidden_layers"]):
        window = cfg["hybrid_layer_pattern"][layer] == 1
        kv = cfg["swa_num_key_value_heads"] if window else cfg["num_key_value_heads"]
        sink = cfg["add_swa_attention_sink_bias"] if window \
            else cfg["add_full_attention_sink_bias"]
        shapes = {"ln1": (d,), "wq": (d, heads * dk), "wk": (d, kv * dk),
                  "wv": (d, kv * dv), "wo": (heads * dv, d), "ln2": (d,)}
        if sink:
            shapes["sink"] = (heads,)
        if cfg["moe_layer_freq"][layer] == 0:
            ff = cfg["intermediate_size"]
            shapes.update(wg=(d, ff), wu=(d, ff), wd=(ff, d))
        else:
            fe = cfg["moe_intermediate_size"]
            shapes.update(router=(d, router), router_bias=(router,),
                          wg=(held, d, fe), wu=(held, d, fe), wd=(held, fe, d))
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
        if name in ("ln1", "ln2", "final_norm"):
            return jnp.ones(shape, dtype)
        return _normal(jax.random.fold_in(key, i), shape=shape, dtype=dtype,
                       std=std)

    return _tree(cfg, leaf)


def param_shapes(cfg: dict, sharding=None):
    """The same pytree as shapes (for compiling with no device to hold it)."""
    dtype = DTYPES[cfg["storage_dtype"]]
    return _tree(cfg, lambda i, name, shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding))
