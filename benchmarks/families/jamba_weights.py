"""Seeded weights of the `jamba` family, made on the device leaf by leaf.

The pytree is the one `models/hybrid_moe_transformer.py` documents for a tied
head: `embed`, `final_norm`, and under `layers` one dict a layer. A state-space
(Mamba) layer holds `ln1`; its mixer's `w_in` ([d, 2 x d_inner]: the x and z
halves side by side), `conv_w` ([d_conv, d_inner], a row a tap, oldest first),
`conv_b`, `w_x` ([d_inner, dt_rank + 2 x d_state]: the step's, B's and C's
columns side by side), the three inner norms `dt_norm`, `b_norm`, `c_norm`,
`w_dt`, `b_dt`, `A_log` ([d_state, d_inner], state-major), `D` and `w_out`;
`ln2` and the dense FFN's `wg`, `wu`, `wd`. An attention layer holds `ln1`,
`wq`, `wk`, `wv`, `wo`, `ln2` and the FFN. The program and the plain reference
are handed the same arrays.

Every matrix, `conv_w` and `conv_b` is normal(0, 0.02) (a configuration may
state another `init_std`; only the rehearsals' tiny sizes do); norm scales are
1; as the published Mamba code makes them, `A_log` row n is log(n + 1), `D` is
1 and `b_dt` is the inverse softplus of steps drawn log-uniform in [0.001,
0.1]. The shapes come from the configuration's published keys alone, so this
file imports nothing of the program."""
import functools
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
NORM_SCALES = ("ln1", "ln2", "dt_norm", "b_norm", "c_norm", "final_norm")
DT_RANGE = (1e-3, 1e-1)


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def is_attention(cfg: dict):
    """Whether each layer is an attention layer (else a state-space one):
    layer i is where i mod `attn_layer_period` == `attn_layer_offset`."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return [i % period == offset for i in range(cfg["num_hidden_layers"])]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mixer_shapes(cfg: dict) -> dict:
    d, di = cfg["hidden_size"], d_inner(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    out = {"w_in": (d, 2 * di), "conv_w": (cfg["mamba_d_conv"], di)}
    if cfg["mamba_conv_bias"]:
        out["conv_b"] = (di,)
    out.update(w_x=(di, r + 2 * n), dt_norm=(r,), b_norm=(n,), c_norm=(n,),
               w_dt=(r, di), b_dt=(di,), A_log=(n, di), D=(di,), w_out=(di, d))
    return out


def leaf_shapes(cfg: dict) -> dict:
    """path -> shape, in a fixed order (the order the keys are dealt in)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dh, kv, ff = head_dim(cfg), cfg["num_key_value_heads"], cfg["intermediate_size"]
    out = {("embed",): (cfg["vocab_size"], d), ("final_norm",): (d,)}
    for layer, attention in enumerate(is_attention(cfg)):
        if attention:
            shapes = {"ln1": (d,), "wq": (d, heads * dh), "wk": (d, kv * dh),
                      "wv": (d, kv * dh), "wo": (heads * dh, d)}
        else:
            shapes = {"ln1": (d,), **mixer_shapes(cfg)}
        shapes.update(ln2=(d,), wg=(d, ff), wu=(d, ff), wd=(ff, d))
        for name, shape in shapes.items():
            out[("layers", layer, name)] = shape
    return out


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, *, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _dt_bias(key, *, shape, dtype):
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


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
        if name in NORM_SCALES or name == "D":
            return jnp.ones(shape, dtype)
        if name == "A_log":
            n = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
            return jnp.broadcast_to(n[:, None], shape).astype(dtype)
        if name == "b_dt":
            return _dt_bias(jax.random.fold_in(key, i), shape=shape, dtype=dtype)
        return _normal(jax.random.fold_in(key, i), shape=shape, dtype=dtype,
                       std=std)

    return _tree(cfg, leaf)


def param_shapes(cfg: dict, sharding=None):
    """The same pytree as shapes (for compiling with no device to hold it)."""
    dtype = DTYPES[cfg["storage_dtype"]]
    return _tree(cfg, lambda i, name, shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding))
