"""The plain reference of the `jamba` family: the forward pass of a decoder
that mixes state-space (Mamba) layers and attention layers with no position
signal, in straightforward `jax.numpy`, float32 at `highest` matmul precision.
One sequence, no cache, no kernels, no batching. It imports nothing of the
program.

The layer equations (from the configuration's published keys; what
`config.json` has no key for is from the published `jamba` modelling code and
is listed under `assumed` in the configuration's file), with RMS norm
n(x; g) = g * x / sqrt(mean(x^2) + rms_norm_eps), d = `hidden_size`,
di = `mamba_expand` x d:

    x0 = embed[token]
    layer i is attention where i mod `attn_layer_period` == `attn_layer_offset`,
    a state-space layer elsewhere; each is pre-norm, h = n(x; ln1):
    state-space: [u | z] = h W_in
        u~_t = silu(sum_j w_j u_{t-d_conv+1+j} + b)   (depthwise, causal, 0 before
                                                   the start; b where `mamba_conv_bias`)
        [dt | B | C] = u~ W_x;  dt, B, C = n(dt; g_dt), n(B; g_B), n(C; g_C)
        dt = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        s_t = exp(dt_t A) * s_{t-1} + (dt_t * u~_t) B_t     (s [d_state, di], s_-1 = 0)
        y_t = s_t C_t + D * u~_t
        out = (y * silu(z)) W_out
    attention: q, k, v = h Wq, h Wk, h Wv (`num_key_value_heads` KV heads of
        d / `num_attention_heads`); ctx = softmax(q k^T / sqrt(head_dim)) v,
        causal, no rotation, no sink; out = ctx Wo
    x = x + out;  x = x + Wd(silu(Wg n(x; ln2)) * Wu n(x; ln2))  (`intermediate_size`)
    logits = n(x; final_norm) embed^T                 (`tie_word_embeddings`)

The recurrence is a sequential `lax.scan` over positions from the zero state.
To fit beside the program's weights on the chip, each layer is a program of
its own, the bfloat16 weights are upcast one matrix at a time, a sequence is
computed at a power of two of positions from 4,096 to 32,768 and at a
multiple of 4,096 past it, the state-space layers run ROWS rows at a time
(carrying the convolution's last inputs and the state from one block of rows
to the next, the scan inside a block still one position after another),
attention over blocks of ROWS query rows, the FFN over blocks of rows, and the
head over blocks of rows.

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
ROWS = 128          # rows a block of the state-space layers and of attention
FFN_ROWS = 2048     # rows a block of the FFN and of the head

Arch = collections.namedtuple("Arch", [
    "attention", "heads", "kv_heads", "head_dim", "eps", "d_inner", "d_state",
    "d_conv", "dt_rank", "conv_bias"])

#: precision name -> (activation dtype, float8 projection operands)
PRECISIONS = {
    "float32": (jnp.float32, False),
    "bfloat16": (jnp.bfloat16, False),
    "float8": (jnp.bfloat16, True),
}


def arch_of(cfg: dict) -> Arch:
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return Arch(
        attention=tuple(i % period == offset
                        for i in range(cfg["num_hidden_layers"])),
        heads=heads, kv_heads=cfg["num_key_value_heads"], head_dim=d // heads,
        eps=float(cfg["rms_norm_eps"]), d_inner=cfg["mamba_expand"] * d,
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        dt_rank=cfg["mamba_dt_rank"], conv_bias=bool(cfg["mamba_conv_bias"]))


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


def _in_blocks(f, x, rows):
    """`f` on every row of `x`, `rows` rows at a time (the rows divide)."""
    s = x.shape[0]
    rows = min(rows, s)
    if s % rows:
        return f(x)
    return jax.lax.map(f, x.reshape(s // rows, rows, -1)).reshape(s, -1)


def _mamba(p, h, arch: Arch, fp8):
    """A state-space mixer on one sequence `h` [s, d], ROWS rows at a time."""
    s, d = h.shape
    di, n, r, taps = arch.d_inner, arch.d_state, arch.dt_rank, arch.d_conv
    rows = math.gcd(ROWS, s)
    f32 = jnp.float32
    w = p["conv_w"].astype(f32)
    a = -jnp.exp(p["A_log"].astype(f32))  # [n, di]
    dd = p["D"].astype(f32)

    def step(state, row):
        dt, u, b, c = row
        state = jnp.exp(dt[None, :] * a) * state + (dt * u)[None, :] * b[:, None]
        return state, (state * c[:, None]).sum(0)

    def block(carry, hb):
        before, state = carry
        uz = _mm(hb, p["w_in"], fp8)
        u, z = uz[:, :di], uz[:, di:]
        seen = jnp.concatenate([before, u.astype(f32)])  # row j: block row j - taps + 1
        conv = sum(w[j] * seen[j:j + rows] for j in range(taps))
        if arch.conv_bias:
            conv = conv + p["conv_b"].astype(f32)
        u = jax.nn.silu(conv)
        dbc = _mm(u.astype(hb.dtype), p["w_x"], fp8)
        dt = _rms_norm(dbc[:, :r], p["dt_norm"], arch.eps)
        b = _rms_norm(dbc[:, r:r + n], p["b_norm"], arch.eps)
        c = _rms_norm(dbc[:, r + n:], p["c_norm"], arch.eps)
        dt = jax.nn.softplus(
            _mm(dt.astype(hb.dtype), p["w_dt"], fp8).astype(f32)
            + p["b_dt"].astype(f32))
        state, y = jax.lax.scan(step, state, (dt, u, b, c))
        y = y + dd * u
        out = _mm((y * jax.nn.silu(z.astype(f32))).astype(hb.dtype),
                  p["w_out"], fp8)
        return (seen[rows:], state), out

    start = (jnp.zeros((taps - 1, di), f32), jnp.zeros((n, di), f32))
    _, out = jax.lax.scan(block, start, h.reshape(s // rows, rows, d))
    return out.reshape(s, d)


def _attention(q, k, v):
    """q [s, H, dh], k, v [s, Hkv, dh] -> [s, H, dh], causal, over blocks of
    ROWS query rows against every key; query head h reads KV head
    h // (H / Hkv)."""
    s, heads, dh = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    rows = math.gcd(ROWS, s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST,
                            preferred_element_type=jnp.float32) / math.sqrt(dh)
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], scores, NEG), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(qb.dtype), v, precision=HIGHEST,
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    return jax.lax.map(one, jnp.arange(0, s, rows)).reshape(s, heads, dh)


def _attention_op(p, h, arch: Arch, fp8):
    s = h.shape[0]
    q = _mm(h, p["wq"], fp8).reshape(s, arch.heads, arch.head_dim)
    k = _mm(h, p["wk"], fp8).reshape(s, arch.kv_heads, arch.head_dim)
    v = _mm(h, p["wv"], fp8).reshape(s, arch.kv_heads, arch.head_dim)
    ctx = _attention(q, k, v).reshape(s, arch.heads * arch.head_dim)
    return _mm(ctx, p["wo"], fp8)


def _ffn(p, h, fp8):
    return _in_blocks(
        lambda x: _mm(jax.nn.silu(_mm(x, p["wg"], fp8)) * _mm(x, p["wu"], fp8),
                      p["wd"], fp8), h, FFN_ROWS)


@functools.partial(jax.jit, static_argnames=("attention", "arch", "fp8"))
def block(p, x, *, attention: bool, arch: Arch, fp8=False):
    """One layer on one sequence `x` [s, d]; `p` holds the layer's weights.
    A program of its own, so that one layer's temporaries are gone before
    the next layer's exist."""
    h = _rms_norm(x, p["ln1"], arch.eps).astype(x.dtype)
    if attention:
        x = x + _attention_op(p, h, arch, fp8)
    else:
        x = x + _mamba(p, h, arch, fp8)
    return x + _ffn(p, _rms_norm(x, p["ln2"], arch.eps).astype(x.dtype), fp8)


def hidden(params, tokens, arch: Arch, precision="float32"):
    """The normed last hidden rows [s, d] of one sequence `tokens` [s] (the
    head's input), one layer after another."""
    dtype, fp8 = PRECISIONS[precision]
    x = params["embed"][tokens].astype(dtype)
    for attention, p in zip(arch.attention, params["layers"]):
        x = block(p, x, attention=attention, arch=arch, fp8=fp8)
    return _rms_norm(x, params["final_norm"], arch.eps).astype(dtype)


def head(params, h, precision="float32"):
    """Logits [s, vocab] (float32) of the normed rows `h`: the embedding,
    read transposed."""
    embed = params["embed"].astype(h.dtype)
    if PRECISIONS[precision][1]:
        h, embed = _fp8(h), _fp8(embed)
    return jnp.matmul(h, embed.T, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def forward(params, tokens, arch: Arch, precision="float32"):
    """Next-token logits [s, vocab] (float32) of one sequence `tokens` [s]."""
    return head(params, hidden(params, tokens, arch, precision), precision)


def _gaps(params, tokens, *, arch, precision):
    """Per position: the float32 best logit less that of the next token, the
    same for the token `precision` puts first, and the logits' spread."""
    h = hidden(params, tokens, arch)
    low = h if precision == "float32" else hidden(params, tokens, arch, precision)
    return _gaps_of(params["embed"], h, low, tokens, precision=precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _gaps_of(embed, h, low, tokens, *, precision):
    """`_gaps` from the normed rows, over blocks of FFN_ROWS rows (a whole
    sequence's logits would not fit)."""
    params = {"embed": embed}
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])

    def one(rows):
        hb, lb, nb = rows
        logits = head(params, hb)
        best = logits.max(-1)
        served = best - jnp.take_along_axis(logits, nb[:, None], 1)[:, 0]
        if precision == "float32":
            low_gap = jnp.zeros_like(served)
        else:
            low_tok = jnp.argmax(head(params, lb, precision), -1)
            low_gap = best - jnp.take_along_axis(logits, low_tok[:, None], 1)[:, 0]
        return jnp.stack([served, low_gap, logits.std(-1)], -1)

    s = tokens.shape[0]
    rows = min(FFN_ROWS, s)
    out = jax.lax.map(one, (h.reshape(s // rows, rows, -1),
                            low.reshape(s // rows, rows, -1),
                            nxt.reshape(s // rows, rows)))
    out = out.reshape(s, 3)
    return out[:, 0], out[:, 1], out[:, 2]


def _bucket(tokens, floor=4096, top=32768, step=4096, margin=128):
    """The length to compute at: the tokens through the last non-zero one
    (prompt ids are never 0) and `margin` more, rounded up to a power of two
    from `floor` to `top`, and past `top` to a multiple of `step` (it may
    pass the width given: the sequence is then padded). Every layer is
    causal, so what is computed is exact at every real position. Five
    lengths at most: each is three more programs in the persistent compile
    cache, which every cell's programs share."""
    import numpy as np

    nonzero = np.flatnonzero(np.asarray(tokens))
    used = min((int(nonzero[-1]) + 1 if nonzero.size else 0) + margin,
               tokens.shape[0])
    length = floor
    while length < min(used, top):
        length *= 2
    return max(length, -(-used // step) * step)


def served_token_gaps(params, tokens, cfg, precision="float32"):
    """For one sequence (prompt + served tokens, zero-padded to a fixed
    width): at every position the float32 reference's best logit minus its
    logit of the token that follows; the same for the token a lower
    `precision` would put first (the control); and the logits' spread. The
    caller keeps the positions that predict served tokens; the padding
    beyond them is not computed and reads 0."""
    width = tokens.shape[0]
    length = _bucket(tokens)
    tokens = jnp.pad(tokens, (0, max(length - width, 0)))[:length]
    out = _gaps(params, tokens, arch=arch_of(cfg), precision=precision)
    return tuple(jnp.pad(a, (0, max(width - length, 0)))[:width] for a in out)
