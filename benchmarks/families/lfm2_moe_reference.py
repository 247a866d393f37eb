"""The plain reference of the `lfm2_moe` family: the forward pass of a decoder
whose layers are gated short convolutions or grouped-query attention, with a
gated FFN that after the leading dense layers is a mixture of experts, in
straightforward `jax.numpy`, float32 at `highest` matmul precision. One
sequence, no cache, no state, no kernels, no batching. It imports nothing of
the program.

The layer equations (from the configuration's published keys; what no key
names is listed under `assumed` in the configuration's file), with RMS norm
n(x; g) = g * x / sqrt(mean(x^2) + norm_eps):

    x <- x + Op_l(n(x; ln1));  x <- x + FFN_l(n(x; ln2));  after the last
    layer a final RMS norm, then the head, which is the embedding transposed.

Op by `layer_types[l]`. `conv`: [B | C | X] = h W_in (thirds of 3d in that
order); u_t = B_t * X_t; c_t = sum_{j < L} w_j * u_{t-(L-1)+j} with u_s = 0
for s < 0 (`conv_L_cache` = L taps, depthwise, causal, no bias); Op = (C_t *
c_t) W_out. `full_attention`: `num_attention_heads` query heads and
`num_key_value_heads` key/value heads of hidden_size / num_attention_heads;
a learned RMS norm over the dims of every query and key head; rotary over the
whole head (half-split layout) at `rope_theta`; scores q.k / sqrt(head),
causal softmax; W_o. FFN: the first `num_dense_layers` published layers
W_d(silu(W_g h) * W_u h) at `intermediate_size`; the others the same at
`moe_intermediate_size` in each of `num_experts` experts: s = sigmoid(h W_r) in
float32, the `num_experts_per_tok` largest of s + b chosen (the bias selects and
does not weigh), weights s[chosen] / (sum s[chosen] + 1e-6) times
`routed_scaling_factor`, and the output the weighted sum of the chosen experts'
FFNs **over the experts held here** (`experts_held`, default all).

To fit beside the program's weights on the chip, the bfloat16 weights are
upcast one matrix at a time, attention runs over blocks of query rows, the
dense FFN over blocks of rows, and an expert runs on the rows that chose it
(gathered to the front of as many rows as the sequence has, one expert after
another).

`precision` other than "float32" is the control: the same mathematics with
activations in bfloat16 and, for "float8", both operands of every projection
rounded to float8 e4m3 with one scale a tensor.
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
ROWS = 128          # query rows a block of attention
FFN_ROWS = 2048     # rows a block of the dense FFN
TOPK_EPS = 1e-6

Arch = collections.namedtuple("Arch", [
    "d", "heads", "kv_heads", "head_dim", "theta", "eps", "taps", "ops",
    "dense", "experts", "per_token", "held", "norm_topk", "routed_scale"])

#: precision name -> (activation dtype, float8 projection operands)
PRECISIONS = {
    "float32": (jnp.float32, False),
    "bfloat16": (jnp.bfloat16, False),
    "float8": (jnp.bfloat16, True),
}


def arch_of(cfg: dict) -> Arch:
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    heads = cfg["num_attention_heads"]
    return Arch(
        d=cfg["hidden_size"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        theta=float(cfg["rope_theta"]), eps=float(cfg["norm_eps"]),
        taps=cfg["conv_L_cache"],
        ops=tuple(cfg["layer_types"][i] for i in kept),
        dense=tuple(i < cfg["num_dense_layers"] for i in kept),
        experts=cfg["num_experts"], per_token=cfg["num_experts_per_tok"],
        held=tuple(cfg.get("experts_held", range(cfg["num_experts"]))),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg.get("routed_scaling_factor") or 1.0))


def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-30)
    q = (x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) / scale).astype(x.dtype)


def _mm(a, w, fp8=False):
    """`a @ w` with `w` upcast to `a`'s dtype here and nowhere earlier."""
    w = w.astype(a.dtype)
    if fp8:
        a, w = _fp8(a), _fp8(w)
    return jnp.matmul(a, w, precision=HIGHEST,
                      preferred_element_type=jnp.float32).astype(a.dtype)


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rotary(x, theta):
    """x [s, heads, dim]: position p rotates dims (i, i + dim/2) by
    p * theta**(-2i/dim)."""
    s, _, dim = x.shape
    half = dim // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v):
    """q [s, H, dh], k, v [s, Hkv, dh] -> [s, H, dh], causal, over blocks of
    ROWS query rows; query head h reads KV head h // (H / Hkv)."""
    s, heads, dh = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    rows = next(r for r in range(min(ROWS, s), 0, -1) if s % r == 0)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST,
                            preferred_element_type=jnp.float32) / math.sqrt(dh)
        i = start + jnp.arange(rows)[:, None]
        j = jnp.arange(s)[None, :]
        scores = jnp.where((j <= i)[None], scores, NEG)
        p = jax.nn.softmax(scores, axis=-1).astype(qb.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST,
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    return jax.lax.map(one, jnp.arange(0, s, rows)).reshape(s, heads, dh)


def _attention_op(p, h, arch: Arch, fp8):
    s = h.shape[0]
    q = _mm(h, p["wq"], fp8).reshape(s, arch.heads, arch.head_dim)
    k = _mm(h, p["wk"], fp8).reshape(s, arch.kv_heads, arch.head_dim)
    v = _mm(h, p["wv"], fp8).reshape(s, arch.kv_heads, arch.head_dim)
    q = _rotary(_rms_norm(q, p["q_norm"], arch.eps).astype(h.dtype), arch.theta)
    k = _rotary(_rms_norm(k, p["k_norm"], arch.eps).astype(h.dtype), arch.theta)
    ctx = _attention(q, k, v)
    return _mm(ctx.reshape(s, arch.heads * arch.head_dim), p["wo"], fp8)


def _conv_op(p, h, arch: Arch, fp8):
    """The gated short convolution, the causal sum written out term by term."""
    s, d = h.shape[0], arch.d
    bcx = _mm(h, p["w_in"], fp8)
    gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = (gate_b * x).astype(jnp.float32)
    w = p["conv_w"].astype(jnp.float32)
    c = jnp.zeros((s, d), jnp.float32)
    for j in range(arch.taps):
        back = arch.taps - 1 - j  # tap j reads the input `back` positions earlier
        c = c + w[j] * jnp.pad(u, ((back, 0), (0, 0)))[:s]
    return _mm((gate_c.astype(jnp.float32) * c).astype(h.dtype), p["w_out"], fp8)


def _gated_ffn(x, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(x, wg, fp8)) * _mm(x, wu, fp8), wd, fp8)


def _dense_ffn(p, x, fp8):
    s = x.shape[0]
    rows = min(FFN_ROWS, s)
    if s % rows:
        return _gated_ffn(x, p["wg"], p["wu"], p["wd"], fp8)
    blocks = x.reshape(s // rows, rows, -1)
    return jax.lax.map(
        lambda xb: _gated_ffn(xb, p["wg"], p["wu"], p["wd"], fp8), blocks
    ).reshape(s, -1)


def route(p, h32, arch: Arch):
    """(chosen [s, k] expert ids, weights [s, k]), in float32."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h32, p["router"].astype(jnp.float32), precision=HIGHEST))
    ranked = jnp.argsort(-(scores + p["router_bias"].astype(jnp.float32)),
                         axis=-1, stable=True)
    chosen = ranked[:, : arch.per_token]
    weights = jnp.take_along_axis(scores, chosen, -1)
    if arch.norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + TOPK_EPS)
    return chosen, weights * arch.routed_scale


def _experts(p, h32, x, arch: Arch, fp8, real=None):
    """The held experts' part of the layer's output for `x` [s, d] (in the
    activation dtype; `h32` the same rows in float32 for the router). Rows
    from `real` on are padding: they reach no expert and read 0."""
    s, d = x.shape
    chosen, weights = route(p, h32, arch)
    if real is not None:
        chosen = jnp.where((jnp.arange(s) < real)[:, None], chosen, -1)
    padded = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])

    def one(out, held):
        """One held expert on the rows that chose it, added into `out`."""
        expert, wg, wu, wd = held
        mine = chosen == expert
        weight = jnp.where(mine, weights, 0.0).sum(-1)
        # the rows that chose it first, then row `s` (zeros) up to `s` rows:
        # seeded weights send most rows to a few experts, so no smaller
        # number of rows is safe
        rows = jnp.nonzero(mine.any(-1), size=s, fill_value=s)[0]
        y = _gated_ffn(padded[rows], wg, wu, wd, fp8).astype(jnp.float32)
        weight = jnp.concatenate([weight, jnp.zeros(1)])[rows]
        return out.at[rows].add(y * weight[:, None]), None

    # one expert after another (a loop the compiler sees once, not 32 times)
    out, _ = jax.lax.scan(
        one, jnp.zeros((s + 1, d), jnp.float32),
        (jnp.asarray(arch.held), p["wg"], p["wu"], p["wd"]))
    return out[:s].astype(x.dtype)


def block(p, x, layer: int, arch: Arch, fp8=False, real=None):
    """One layer on one sequence `x` [s, d]; `p` holds the layer's weights."""
    h = _rms_norm(x, p["ln1"], arch.eps).astype(x.dtype)
    op = _conv_op if arch.ops[layer] == "conv" else _attention_op
    x = x + op(p, h, arch, fp8)
    h32 = _rms_norm(x, p["ln2"], arch.eps)
    h = h32.astype(x.dtype)
    if arch.dense[layer]:
        return x + _dense_ffn(p, h, fp8)
    return x + _experts(p, h32, h, arch, fp8, real)


def forward(params, tokens, arch: Arch, precision="float32", real=None):
    """Next-token logits [s, vocab] (float32) of one sequence `tokens` [s],
    of which the first `real` are the sequence and the rest padding (causal:
    the padding moves nothing before it)."""
    dtype, fp8 = PRECISIONS[precision]
    x = params["embed"][tokens].astype(dtype)
    for layer, p in enumerate(params["layers"]):
        x = block(p, x, layer, arch, fp8, real)
    h = _rms_norm(x, params["final_norm"], arch.eps).astype(dtype)
    head = params["embed"].astype(dtype).T  # tied
    if fp8:
        h, head = _fp8(h), _fp8(head)
    return jnp.matmul(h, head, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _gaps(params, tokens, real, *, arch, precision):
    logits = forward(params, tokens, arch, real=real)
    best = logits.max(-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    gap_served = best - jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
    if precision == "float32":
        return gap_served, jnp.zeros_like(gap_served), logits.std(-1)
    low = forward(params, tokens, arch, precision, real=real)
    low_tok = jnp.argmax(low, -1)
    gap_low = best - jnp.take_along_axis(logits, low_tok[:, None], 1)[:, 0]
    return gap_served, gap_low, logits.std(-1)


def _bucket(tokens, floor=1024, margin=128):
    """(the length to compute at, the rows of it that may be real): the
    tokens through the last non-zero one (prompt ids are never 0) and
    `margin` more are taken as real, and the length is that rounded up to a
    power of two from `floor` (it may pass the width given: the sequence is
    then padded). Every layer is causal, so what is computed is exact at
    every real position."""
    import numpy as np

    nonzero = np.flatnonzero(np.asarray(tokens))
    used = min((int(nonzero[-1]) + 1 if nonzero.size else 0) + margin,
               tokens.shape[0])
    length = floor
    while length < used:
        length *= 2
    return length, used


def served_token_gaps(params, tokens, cfg, precision="float32"):
    """For one sequence (prompt + served tokens, zero-padded to a fixed
    width): at every position the float32 reference's best logit minus its
    logit of the token that follows; the same for the token a lower
    `precision` would put first (the control); and the logits' spread. The
    caller keeps the positions that predict served tokens; the padding
    beyond them is not computed and reads 0."""
    width = tokens.shape[0]
    length, used = _bucket(tokens)
    tokens = jnp.pad(tokens, (0, max(length - width, 0)))[:length]
    out = _gaps(params, tokens, jnp.int32(used), arch=arch_of(cfg),
                precision=precision)
    return tuple(jnp.pad(a, (0, max(width - length, 0)))[:width] for a in out)
