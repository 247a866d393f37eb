"""The plain reference of the `afmoe` family: the forward pass of a decoder
that mixes window and full attention layers, gates the attention's output,
norms before and after every operator, and in most layers adds an always-on
shared expert to a sum over sparse routed ones, in straightforward
`jax.numpy`, float32 at `highest` matmul precision. One sequence, no cache, no
ring, no kernels, no batching. It imports nothing of the program.

The layer equations (from the configuration's published keys; what
`config.json` has no key for is from the published `afmoe` modelling code and
is listed under `assumed` in the configuration's file), with RMS norm
n(x; g) = g * x / sqrt(mean(x^2) + rms_norm_eps), d = `hidden_size`:

    x0 = embed[token] * sqrt(d)                       (`mup_enabled`)
    h = n(x; ln1);  q, k, v = h Wq, h Wk, h Wv;  g = h Wgate
    q, k = n over every head (q_norm, k_norm)
    `sliding_attention` layers only: q, k = rotary(q, k) over the whole head
        (half-split layout) at `rope_theta`; `full_attention` layers have no
        position signal at all
    ctx = softmax(q k^T / sqrt(head_dim)) v, causal, in a sliding layer only
        keys j with i - `sliding_window` < j <= i; no sink
    x = x + n((ctx * sigmoid(g)) Wo; ln1_post)
    h = n(x; ln2)
    the first `num_dense_layers` published layers: f = Wd(silu(Wg h) * Wu h)
        at `intermediate_size`
    the others: s = sigmoid(h Wr) in float32 over ALL experts; the
        `num_experts_per_tok` largest of s + b chosen (the bias selects and
        does not weigh); w = s[chosen] / (sum s[chosen] + 1e-20) (`route_norm`)
        times `route_scale`; f = shared(h) + sum_chosen w_e * expert_e(h)
        **over the experts held here** (`experts_held`, default all): the
        shared expert (`num_shared_experts` x `moe_intermediate_size` wide)
        is every token's, whole on every chip
    x = x + n(f; ln2_post)
    logits = n(x; final_norm) Whead                   (untied)

To fit beside the program's weights on the chip, the bfloat16 weights are
upcast one matrix at a time, attention runs over blocks of query rows with the
window as a mask, the dense FFN over blocks of rows, and an expert runs on the
rows that chose it (gathered to the front of as many rows as the sequence has,
one expert after another).

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
TOPK_EPS = 1e-20

Arch = collections.namedtuple("Arch", [
    "heads", "kv_heads", "head_dim", "theta", "eps", "window", "ops",
    "dense", "experts", "per_token", "held", "route_norm", "route_scale",
    "shared", "embed_scale"])

#: precision name -> (activation dtype, float8 projection operands)
PRECISIONS = {
    "float32": (jnp.float32, False),
    "bfloat16": (jnp.bfloat16, False),
    "float8": (jnp.bfloat16, True),
}


def arch_of(cfg: dict) -> Arch:
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    d = cfg["hidden_size"]
    return Arch(
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        window=cfg["sliding_window"],
        ops=tuple(cfg["layer_types"][i] for i in kept),
        dense=tuple(i < cfg["num_dense_layers"] for i in kept),
        experts=cfg.get("num_experts_published", cfg["num_experts"]),
        per_token=cfg["num_experts_per_tok"],
        held=tuple(cfg.get("experts_held", range(cfg["num_experts"]))),
        route_norm=bool(cfg["route_norm"]),
        route_scale=float(cfg.get("route_scale") or 1.0),
        shared=cfg["num_shared_experts"],
        embed_scale=math.sqrt(d) if cfg.get("mup_enabled") else 1.0)


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


def _attention(q, k, v, window):
    """q [s, H, dh], k, v [s, Hkv, dh] -> [s, H, dh], causal, over blocks of
    ROWS query rows against every key, the window (None = full) a mask; query
    head h reads KV head h // (H / Hkv)."""
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
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        scores = jnp.where(seen[None], scores, NEG)
        p = jax.nn.softmax(scores, axis=-1).astype(qb.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST,
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    return jax.lax.map(one, jnp.arange(0, s, rows)).reshape(s, heads, dh)


def _attention_op(p, h, sliding: bool, arch: Arch, fp8):
    s = h.shape[0]
    q = _mm(h, p["wq"], fp8).reshape(s, arch.heads, arch.head_dim)
    k = _mm(h, p["wk"], fp8).reshape(s, arch.kv_heads, arch.head_dim)
    v = _mm(h, p["wv"], fp8).reshape(s, arch.kv_heads, arch.head_dim)
    gate = jax.nn.sigmoid(_mm(h, p["w_gate"], fp8).astype(jnp.float32))
    q = _rms_norm(q, p["q_norm"], arch.eps).astype(h.dtype)
    k = _rms_norm(k, p["k_norm"], arch.eps).astype(h.dtype)
    if sliding:
        q, k = _rotary(q, arch.theta), _rotary(k, arch.theta)
    ctx = _attention(q, k, v, arch.window if sliding else None)
    ctx = ctx.reshape(s, arch.heads * arch.head_dim).astype(jnp.float32)
    return _mm((ctx * gate).astype(h.dtype), p["wo"], fp8)


def _gated_ffn(x, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(x, wg, fp8)) * _mm(x, wu, fp8), wd, fp8)


def _in_row_blocks(x, wg, wu, wd, fp8):
    """A gated FFN on every row of `x`, FFN_ROWS rows at a time."""
    s = x.shape[0]
    rows = min(FFN_ROWS, s)
    if s % rows:
        return _gated_ffn(x, wg, wu, wd, fp8)
    blocks = x.reshape(s // rows, rows, -1)
    return jax.lax.map(
        lambda xb: _gated_ffn(xb, wg, wu, wd, fp8), blocks).reshape(s, -1)


def route(p, h32, arch: Arch):
    """(chosen [s, k] expert ids, weights [s, k]), in float32."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h32, p["router"].astype(jnp.float32), precision=HIGHEST))
    ranked = jnp.argsort(-(scores + p["router_bias"].astype(jnp.float32)),
                         axis=-1, stable=True)
    chosen = ranked[:, : arch.per_token]
    weights = jnp.take_along_axis(scores, chosen, -1)
    if arch.route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + TOPK_EPS)
    return chosen, weights * arch.route_scale


def _routed(p, h32, x, arch: Arch, fp8, real=None):
    """The held routed experts' part of the layer's output for `x` [s, d] (in
    the activation dtype; `h32` the same rows in float32 for the router).
    Rows from `real` on are padding: they reach no routed expert."""
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

    # one expert after another (a loop the compiler sees once)
    out, _ = jax.lax.scan(
        one, jnp.zeros((s + 1, d), jnp.float32),
        (jnp.asarray(arch.held), p["wg"], p["wu"], p["wd"]))
    return out[:s]


def shared_expert(p, h, fp8=False):
    """The always-on expert on every row of `h` (float32 out)."""
    return _in_row_blocks(h, p["shared_wg"], p["shared_wu"], p["shared_wd"],
                          fp8).astype(jnp.float32)


def block(p, x, layer: int, arch: Arch, fp8=False, real=None):
    """One layer on one sequence `x` [s, d]; `p` holds the layer's weights."""
    h = _rms_norm(x, p["ln1"], arch.eps).astype(x.dtype)
    a = _attention_op(p, h, arch.ops[layer] == "sliding_attention", arch, fp8)
    x = x + _rms_norm(a, p["ln1_post"], arch.eps).astype(x.dtype)
    h32 = _rms_norm(x, p["ln2"], arch.eps)
    h = h32.astype(x.dtype)
    if arch.dense[layer]:
        f = _in_row_blocks(h, p["wg"], p["wu"], p["wd"], fp8)
    else:
        f = _routed(p, h32, h, arch, fp8, real)
        if arch.shared:
            f = f + shared_expert(p, h, fp8)
    return x + _rms_norm(f, p["ln2_post"], arch.eps).astype(x.dtype)


def forward(params, tokens, arch: Arch, precision="float32", real=None):
    """Next-token logits [s, vocab] (float32) of one sequence `tokens` [s],
    of which the first `real` are the sequence and the rest padding (causal:
    the padding moves nothing before it)."""
    dtype, fp8 = PRECISIONS[precision]
    x = (params["embed"][tokens].astype(jnp.float32)
         * arch.embed_scale).astype(dtype)
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
