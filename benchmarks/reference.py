"""The plain reference: the architecture's forward pass, loss, gradients and
AdamW in straightforward `jax.numpy`, float32 at `highest` matmul precision.

It imports nothing of the program. No kernels, no cache, no batching tricks:
it runs one sequence at a time, layer by layer, so that it fits beside
nothing else on the chip after the window has closed.

Departures from the published OPT block, shared with the program under test
and listed in the configuration files: no biases (the source sets
`enable_bias` false), LayerNorm with a scale and no bias, eps 1e-6, no final
LayerNorm before the head, an output head untied from the embedding, and no
position offset of 2.

`precision="bfloat16"` is the control of "How correct is decided": the same
mathematics with weights and activations in bfloat16 (f32 accumulation inside
a matmul, f32 LayerNorm statistics and softmax), the step below the float32
the configurations state.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _layer_norm(x, scale):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, as an fp8 matmul
    would see its operand; the result keeps x's dtype."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-30)
    q = (x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
    rounded = (q.astype(jnp.float32) / scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)  # gradients pass straight through


def _mm(a, b, fp8=False):
    b = b.astype(a.dtype)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32).astype(a.dtype)


#: precision name -> (activation dtype, fp8 matmul operands)
PRECISIONS = {
    "float32": (jnp.float32, False),
    "bfloat16": (jnp.bfloat16, False),
    "float8": (jnp.bfloat16, True),
}


def block(p, x, *, num_heads, fp8=False):
    """One pre-LN block on one sequence `x` [s, d]; `p` holds one layer."""
    s, d = x.shape
    hd = d // num_heads
    h = _layer_norm(x, p["ln1"])
    q, k, v = jnp.split(_mm(h, p["qkv"], fp8), 3, axis=-1)
    q, k, v = (t.reshape(s, num_heads, hd).transpose(1, 0, 2) for t in (q, k, v))
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("hqk,hkd->hqd", attn, v, precision=HIGHEST,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    x = x + _mm(ctx.transpose(1, 0, 2).reshape(s, d), p["proj"], fp8)
    h = _layer_norm(x, p["ln2"])
    return x + _mm(jax.nn.gelu(_mm(h, p["w_in"], fp8), approximate=False),
                   p["w_out"], fp8)


def forward(params, tokens, *, num_heads, precision="float32", remat=False):
    """Next-token logits [s, vocab] (float32) of one sequence `tokens` [s]."""
    dtype, fp8 = PRECISIONS[precision]
    x = (params["embed"][tokens] + params["pos"][: tokens.shape[0]]).astype(dtype)

    def body(x, p):
        return block(p, x, num_heads=num_heads, fp8=fp8), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x,
                        params["blocks"])
    head = params["head"].astype(dtype)
    if fp8:
        x, head = _fp8(x), _fp8(head)
    return jnp.matmul(x, head, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_heads", "precision"))
def served_token_gaps(params, tokens, *, num_heads, precision="float32"):
    """For one sequence (prompt + served tokens, padded to a fixed length):
    at every position the float32 reference's best logit minus its logit of
    the token that actually follows; the same for the token a lower
    `precision` would put first (the control); and the logits' spread. The
    caller keeps the positions that predict served tokens."""
    logits = forward(params, tokens, num_heads=num_heads)
    best = logits.max(-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    gap_served = best - jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
    if precision == "float32":
        return gap_served, jnp.zeros_like(gap_served), logits.std(-1)
    low = forward(params, tokens, num_heads=num_heads, precision=precision)
    low_tok = jnp.argmax(low, -1)
    gap_low = best - jnp.take_along_axis(logits, low_tok[:, None], 1)[:, 0]
    return gap_served, gap_low, logits.std(-1)


# -- training ---------------------------------------------------------------

def sequence_loss_sum(params, tokens, *, num_heads, precision="float32"):
    """Sum over positions of the next-token cross-entropy of one sequence."""
    logits = forward(params, tokens, num_heads=num_heads, precision=precision,
                     remat=True)[:-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[1:, None], 1)[:, 0]
    return (logz - picked).sum()


@functools.partial(jax.jit, static_argnames=("num_heads", "precision"))
def loss_and_grads(params, batch, *, num_heads, precision="float32"):
    """Mean next-token loss over `batch` [b, s] and its gradients, one row at
    a time (a scan over rows, each row's layers rematerialized) so that the
    reference fits on a chip."""
    b, s = batch.shape
    row = functools.partial(sequence_loss_sum, num_heads=num_heads,
                            precision=precision)

    def body(carry, tokens):
        loss, grads = carry
        l, g = jax.value_and_grad(row)(params, tokens)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0), zero), batch)
    n = b * (s - 1)
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "weight_decay",
                                             "clip"))
def adamw_step(params, mu, nu, grads, count, lr, *, b1, b2, eps, weight_decay,
               clip):
    """One AdamW update after global-norm clipping, as the job file states
    it. Returns (params, mu, nu, clipped grads)."""
    norm = global_norm(grads)
    scale = jnp.where(norm > clip, clip / norm, 1.0) if clip else 1.0
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    t = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p
        return p - lr * step

    return jax.tree_util.tree_map(upd, params, mu, nu), mu, nu, grads
