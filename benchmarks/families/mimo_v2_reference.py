"""The plain reference of the `mimo_v2` family: the forward pass of a decoder
that mixes window and full attention layers and routes its FFN over sparse
experts, in straightforward `jax.numpy`, float32 at `highest` matmul
precision. One sequence, no cache, no kernels, no batching. It imports
nothing of the program.

The layer equations (from the configuration's published keys; what the
config leaves open is listed under `assumed` in the configuration's file):

    h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h));  after the last
    layer a final RMSNorm, then the untied head.

Attention, kind by `hybrid_layer_pattern[l]` (0 full, 1 window): 64 query
heads of 192; keys in `num_key_value_heads` (full) or `swa_num_key_value_heads`
(window) heads of 192, values in as many heads of `v_head_dim`, scaled by
`attention_value_scale`; rotary on the first int(192 * partial_rotary_factor)
dims of every query and key head (half-split layout) with `rope_theta` or
`swa_rope_theta`; scores q.k / sqrt(192), causal, in a window layer only keys
j with i - window < j <= i; where the kind has a sink, the head's learned
logit joins the softmax as a column that is then dropped. FFN by
`moe_layer_freq[l]`: 0 the gated dense FFN, 1 the experts: s = sigmoid(x Wr)
in float32 over ALL experts, the `num_experts_per_tok` largest of s + b chosen,
weights s[chosen] / sum (`norm_topk_prob`), and the output the weighted sum
of the chosen experts' gated FFNs **over the experts this chip holds**
(`experts_held`): what the absent experts would add is left out, as in the
program.

To fit beside the program's weights on the chip at 16,896 positions, the
bfloat16 weights are upcast one matrix at a time, attention runs over blocks
of query rows (a window layer reading only the keys its rows can see), the
dense FFN over blocks of rows, and an expert runs on the rows that chose it
(gathered up to a fixed capacity; a count above it turns the result to NaN,
so it cannot pass unseen).

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

Arch = collections.namedtuple("Arch", [
    "heads", "k_dim", "v_dim", "rotary_dim", "kv_full", "kv_window", "window",
    "theta_full", "theta_window", "sink_full", "sink_window", "value_scale",
    "eps", "attn_kinds", "ffn_kinds", "experts", "per_token", "held",
    "norm_topk", "routed_scale"])

#: precision name -> (activation dtype, float8 projection operands)
PRECISIONS = {
    "float32": (jnp.float32, False),
    "bfloat16": (jnp.bfloat16, False),
    "float8": (jnp.bfloat16, True),
}


def arch_of(cfg: dict) -> Arch:
    layers = cfg["num_hidden_layers"]
    held = cfg.get("experts_held")
    if held is None:
        held = range(cfg["n_routed_experts"])
    return Arch(
        heads=cfg["num_attention_heads"], k_dim=cfg["head_dim"],
        v_dim=cfg["v_head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        kv_full=cfg["num_key_value_heads"],
        kv_window=cfg["swa_num_key_value_heads"],
        window=cfg["sliding_window"], theta_full=float(cfg["rope_theta"]),
        theta_window=float(cfg["swa_rope_theta"]),
        sink_full=bool(cfg["add_full_attention_sink_bias"]),
        sink_window=bool(cfg["add_swa_attention_sink_bias"]),
        value_scale=float(cfg["attention_value_scale"]),
        eps=float(cfg["layernorm_epsilon"]),
        attn_kinds=tuple(cfg["hybrid_layer_pattern"][:layers]),
        ffn_kinds=tuple(cfg["moe_layer_freq"][:layers]),
        experts=cfg.get("n_routed_experts_published", cfg["n_routed_experts"]),
        per_token=cfg["num_experts_per_tok"], held=tuple(held),
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


def _rotary(x, rotary_dim, theta):
    """x [s, heads, dim]: position p rotates dims (i, i + rotary_dim/2) of
    the first `rotary_dim` by p * theta**(-2i/rotary_dim)."""
    s = x.shape[0]
    half = rotary_dim // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary_dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:rotary_dim], x32[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           -1).astype(x.dtype)


def _softmax_with_sink(scores, sink):
    """Softmax over the last axis of `scores` [heads, rows, keys]; with a
    `sink` [heads] the sink's logit is one more column, dropped afterwards."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    column = jnp.broadcast_to(sink.astype(jnp.float32)[:, None, None],
                              scores.shape[:2] + (1,))
    return jax.nn.softmax(jnp.concatenate([scores, column], -1), -1)[..., :-1]


def _attention(q, k, v, sink, window):
    """q [s, H, dk], k [s, Hkv, dk], v [s, Hkv, dv] -> [s, H, dv], causal,
    over blocks of ROWS query rows; `window` None = full. A window layer's
    block reads the keys from `window` before its first row to its last."""
    s, heads, dk = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)  # query head h reads KV head h // group
    v = jnp.repeat(v, group, axis=1)
    rows = next(r for r in range(min(ROWS, s), 0, -1) if s % r == 0)
    back = 0 if window is None else window
    if window is not None:
        pad = ((back, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    span = s if window is None else rows + back

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        if window is None:
            kb, vb, first = k, v, 0
        else:
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, 0)
            first = start - back  # position of kb's first key
        scores = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HIGHEST,
                            preferred_element_type=jnp.float32) / math.sqrt(dk)
        i = start + jnp.arange(rows)[:, None]
        j = first + jnp.arange(span)[None, :]
        seen = (j <= i) & (j >= 0)
        if window is not None:
            seen = seen & (j > i - window)
        scores = jnp.where(seen[None], scores, NEG)
        p = _softmax_with_sink(scores, sink).astype(qb.dtype)
        return jnp.einsum("hqk,khd->qhd", p, vb, precision=HIGHEST,
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    out = jax.lax.map(one, jnp.arange(0, s, rows))
    return out.reshape(s, heads, v.shape[-1])


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
        weights = weights / weights.sum(-1, keepdims=True)
    return chosen, weights * arch.routed_scale


def _experts(p, h32, x, arch: Arch, fp8, real=None):
    """The held experts' part of the layer's output for `x` [s, d] (in the
    activation dtype; `h32` the same rows in float32 for the router). Rows
    from `real` on are padding: they reach no expert (a run of equal padding
    tokens would all pick the same experts and fill them) and read 0."""
    s, d = x.shape
    chosen, weights = route(p, h32, arch)
    if real is not None:
        chosen = jnp.where((jnp.arange(s) < real)[:, None], chosen, -1)
    capacity = s if s <= 2048 else s // 4
    padded = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    out = jnp.zeros((s + 1, d), jnp.float32)
    overflow = jnp.bool_(False)
    for slot, expert in enumerate(arch.held):
        mine = chosen == expert
        weight = jnp.where(mine, weights, 0.0).sum(-1)
        rows = jnp.nonzero(mine.any(-1), size=capacity, fill_value=s)[0]
        overflow = overflow | (mine.any(-1).sum() > capacity)
        y = _gated_ffn(padded[rows], p["wg"][slot], p["wu"][slot],
                       p["wd"][slot], fp8).astype(jnp.float32)
        weight = jnp.concatenate([weight, jnp.zeros(1)])[rows]
        out = out.at[rows].add(y * weight[:, None])
    return jnp.where(overflow, jnp.nan, out[:s]).astype(x.dtype)


def block(p, x, layer: int, arch: Arch, fp8=False, real=None):
    """One layer on one sequence `x` [s, d]; `p` holds the layer's weights."""
    s = x.shape[0]
    window = arch.attn_kinds[layer] == 1
    kv = arch.kv_window if window else arch.kv_full
    theta = arch.theta_window if window else arch.theta_full
    has_sink = arch.sink_window if window else arch.sink_full
    h = _rms_norm(x, p["ln1"], arch.eps).astype(x.dtype)
    q = _mm(h, p["wq"], fp8).reshape(s, arch.heads, arch.k_dim)
    k = _mm(h, p["wk"], fp8).reshape(s, kv, arch.k_dim)
    v = (arch.value_scale * _mm(h, p["wv"], fp8)).astype(x.dtype).reshape(
        s, kv, arch.v_dim)
    q = _rotary(q, arch.rotary_dim, theta)
    k = _rotary(k, arch.rotary_dim, theta)
    ctx = _attention(q, k, v, p["sink"] if has_sink else None,
                     arch.window if window else None)
    x = x + _mm(ctx.reshape(s, arch.heads * arch.v_dim), p["wo"], fp8)
    h32 = _rms_norm(x, p["ln2"], arch.eps)
    h = h32.astype(x.dtype)
    if arch.ffn_kinds[layer] == 0:
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
    head = params["head"].astype(dtype)
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
    power of two from `floor`, at most the width given. Attention is causal,
    so what is computed is exact at every real position."""
    import numpy as np

    width = tokens.shape[0]
    nonzero = np.flatnonzero(np.asarray(tokens))
    used = min((int(nonzero[-1]) + 1 if nonzero.size else 0) + margin, width)
    length = floor
    while length < used:
        length *= 2
    return min(length, width), used


def served_token_gaps(params, tokens, cfg, precision="float32"):
    """For one sequence (prompt + served tokens, zero-padded to a fixed
    width): at every position the float32 reference's best logit minus its
    logit of the token that follows; the same for the token a lower
    `precision` would put first (the control); and the logits' spread. The
    caller keeps the positions that predict served tokens; the padding
    beyond them is not computed and reads 0."""
    width = tokens.shape[0]
    length, used = _bucket(tokens)
    out = _gaps(params, tokens[:length], jnp.int32(used), arch=arch_of(cfg),
                precision=precision)
    return tuple(jnp.pad(a, (0, width - length)) for a in out)
