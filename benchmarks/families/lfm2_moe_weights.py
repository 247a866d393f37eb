"""Seeded weights of the `lfm2_moe` family, made on the device leaf by leaf.

The pytree is the one `models/hybrid_moe_transformer.py` documents for a
tied head: `embed` and `final_norm` (no `head`: the head is the embedding),
and under `layers` one dict a layer: `ln1`, then a convolution layer's `w_in`
([B | C | X] thirds), `conv_w` (a row a tap, oldest first) and `w_out`, or an
attention layer's `wq`, `wk`, `wv`, `wo`, `q_norm`, `k_norm`; `ln2`; then the
dense FFN's `wg`, `wu`, `wd` or an expert layer's `router`, `router_bias` and
`wg`, `wu`, `wd` with the held experts leading. The program and the plain
reference are handed the same arrays. Every matrix, every tap and the
router's correction bias is normal(0, 0.02) (a non-zero bias, so that
selecting by s + b and weighing by s differ; a configuration may state
another `init_std`; only the rehearsals' tiny sizes do, to have logits apart);
norm scales are 1. The shapes come from the configuration's published keys
alone, so this file imports nothing of the program."""
import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
NORM_SCALES = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def head_dim(cfg: dict) -> int:
    """The width of a head: the file's `head_dim`, else hidden / heads."""
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layers_of(cfg: dict):
    """(operator, dense FFN?) of every layer that is run: the published
    layers `layers_kept` names, or the first `num_hidden_layers`."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i], i < cfg["num_dense_layers"]) for i in kept]


def leaf_shapes(cfg: dict) -> dict:
    """path -> shape, in a fixed order (the order the keys are dealt in)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dh, kv = head_dim(cfg), cfg["num_key_value_heads"]
    held = len(cfg.get("experts_held", range(cfg["num_experts"])))
    out = {("embed",): (cfg["vocab_size"], d), ("final_norm",): (d,)}
    for layer, (op, dense) in enumerate(layers_of(cfg)):
        if op == "conv":
            shapes = {"ln1": (d,), "w_in": (d, 3 * d),
                      "conv_w": (cfg["conv_L_cache"], d), "w_out": (d, d)}
        else:
            shapes = {"ln1": (d,), "wq": (d, heads * dh), "wk": (d, kv * dh),
                      "wv": (d, kv * dh), "wo": (heads * dh, d),
                      "q_norm": (dh,), "k_norm": (dh,)}
        shapes["ln2"] = (d,)
        if dense:
            ff = cfg["intermediate_size"]
            shapes.update(wg=(d, ff), wu=(d, ff), wd=(ff, d))
        else:
            fe, router = cfg["moe_intermediate_size"], cfg["num_experts"]
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
