"""Operations and bytes of the `jamba` family, computed from shapes: what one
token multiplies, the least bytes a decode step and its grouped-query decode
kernel have to read, and the least bytes the state-space layers' chunk scan
has to move."""
import math

from jamba_weights import d_inner, head_dim, is_attention, leaf_shapes, mixer_shapes

#: a state-space mixer's leaves that a token multiplies as a matrix
MIXER_MATRICES = ("w_in", "w_x", "w_dt", "w_out")


def full_layers(cfg) -> int:
    return sum(is_attention(cfg))


def ssm_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - full_layers(cfg)


def attention_params(cfg) -> int:
    """Wq and Wo at the query heads' width, Wk and Wv at the KV heads'."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * heads * dh + 2 * d * kv * dh


def mixer_matmul_params(cfg) -> int:
    shapes = mixer_shapes(cfg)
    return sum(math.prod(shapes[name]) for name in MIXER_MATRICES)


def ffn_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def held_params(cfg) -> int:
    """Every parameter held (the embedding once: it is the head too)."""
    return sum(math.prod(s) for s in leaf_shapes(cfg).values())


def matmul_params(cfg: dict) -> float:
    """Parameters one token multiplies: every layer's FFN, the attention
    layers' four projections, the state-space mixers' four (the convolution's
    taps, the norms, A and D are elementwise), and the head (the embedding,
    read transposed)."""
    return (cfg["num_hidden_layers"] * ffn_params(cfg)
            + full_layers(cfg) * attention_params(cfg)
            + ssm_layers(cfg) * mixer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def serve_token_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token that attends over `context` positions:
    the matrices, and scores and values of every query head in every attention
    layer (the scan's elementwise work is left out, as MFU leaves it out)."""
    heads, dh = cfg["num_attention_heads"], head_dim(cfg)
    return (2.0 * matmul_params(cfg)
            + 2.0 * heads * 2 * dh * full_layers(cfg) * context)


def kv_position_bytes(cfg, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one position holds in ONE attention layer."""
    return cfg["num_key_value_heads"] * 2 * head_dim(cfg) * dtype_bytes


def lane_state_bytes(cfg, dtype_bytes: int = 2) -> int:
    """Bytes a slot holds over every state-space layer: its convolution's last
    `mamba_d_conv - 1` inputs in the cache's dtype and its scan's state of
    `mamba_d_state x d_inner` in float32."""
    di = d_inner(cfg)
    return ssm_layers(cfg) * di * ((cfg["mamba_d_conv"] - 1) * dtype_bytes
                                   + cfg["mamba_d_state"] * 4)


def gqa_decode_call(cfg: dict, contexts, dtype_bytes: int = 2) -> dict:
    """One grouped-query paged decode-attention call of ONE attention layer:
    each live slot reads the K and V of its `context` positions once."""
    positions = sum(contexts)
    return {"flops": 2.0 * cfg["num_attention_heads"] * 2 * head_dim(cfg) * positions,
            "bytes": positions * kv_position_bytes(cfg, dtype_bytes)}


def decode_step_bytes(cfg: dict, contexts, experts_touched: float = 0.0,
                      dtype_bytes: int = 2) -> float:
    """The least a decode step reads and writes: every weight once, and for
    every live lane (one entry of `contexts` a lane) the K/V of its `context`
    positions in each attention layer and its whole state, read and written
    back. There are no experts: `experts_touched` adds nothing."""
    weights = held_params(cfg) * dtype_bytes
    kv = sum(contexts) * full_layers(cfg) * kv_position_bytes(cfg, dtype_bytes)
    return weights + kv + 2 * len(contexts) * lane_state_bytes(cfg, dtype_bytes)


def ssm_scan_call(cfg: dict, rows: float) -> dict:
    """The least bytes ONE call of the chunk scan (`ssm_scan_chunk_*`, one
    state-space layer, `rows` rows) moves, all in float32: the step and the
    convolved inputs [rows, d_inner] and B, C [rows, d_state] in, y [rows,
    d_inner] out, A [d_state, d_inner] and D [d_inner] in, and the slot's state
    [d_state, d_inner] in and out."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    return {"bytes": 4.0 * (rows * (3 * di + 2 * n) + 3 * n * di + di)}
