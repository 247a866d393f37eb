"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself: the program and the plain reference
are both handed these arrays, so neither takes anything the other made. The
pytree has the layout the program's `init_params` documents (block weights
stacked on a leading layer dim)."""
import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02  # the OPT/Galactica initializer range


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("L", "d", "ff", "vocab", "max_len"))
def _make(key, *, L, d, ff, vocab, max_len):
    keys = jax.random.split(key, 7)

    def nrm(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * INIT_STD

    return {
        "embed": nrm(keys[0], (vocab, d)),
        "pos": nrm(keys[1], (max_len, d)),
        "blocks": {
            "qkv": nrm(keys[2], (L, d, 3 * d)),
            "proj": nrm(keys[3], (L, d, d)),
            "w_in": nrm(keys[4], (L, d, ff)),
            "w_out": nrm(keys[5], (L, ff, d)),
            "ln1": jnp.ones((L, d), jnp.float32),
            "ln2": jnp.ones((L, d), jnp.float32),
        },
        "head": nrm(keys[6], (d, vocab)),
    }


def make_params(seed: int, cfg: dict):
    return _make(
        seed_key(seed),
        L=cfg["num_hidden_layers"], d=cfg["hidden_size"], ff=cfg["ffn_dim"],
        vocab=cfg["vocab_size"], max_len=cfg["max_position_embeddings"],
    )


def param_shapes(cfg: dict, sharding=None):
    """The same pytree as shapes (for compiling with no device to hold it)."""
    shapes = jax.eval_shape(lambda: make_params(0, cfg))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), shapes)
