"""Operations and bytes of the `lfm2_moe` family, computed from shapes: what
one token multiplies here, and the least bytes a decode step and its
grouped-query decode kernel have to read."""
from lfm2_moe_weights import head_dim as _head_dim
from lfm2_moe_weights import layers_of


def conv_params(cfg) -> int:
    """A convolution operator's matrices: `W_in` (d x 3d) and `W_out`."""
    return 4 * cfg["hidden_size"] ** 2


def attention_params(cfg) -> int:
    d, dh = cfg["hidden_size"], _head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * heads * dh + 2 * d * kv * dh


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg) -> float:
    """Of a token's chosen experts, the expected share held here."""
    held = len(cfg.get("experts_held", range(cfg["num_experts"])))
    return held / cfg["num_experts"]


def _outside_experts(cfg) -> int:
    """Every weight a token multiplies outside the experts: the operators,
    the dense FFNs, the routers and the head (the embedding, read whole)."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for op, dense in layers_of(cfg):
        total += conv_params(cfg) if op == "conv" else attention_params(cfg)
        total += 3 * d * cfg["intermediate_size"] if dense else d * cfg["num_experts"]
    return total


def expert_layers(cfg) -> int:
    return sum(1 for _, dense in layers_of(cfg) if not dense)


def full_layers(cfg) -> int:
    return sum(1 for op, _ in layers_of(cfg) if op != "conv")


def conv_layers(cfg) -> int:
    return sum(1 for op, _ in layers_of(cfg) if op == "conv")


def matmul_params(cfg: dict) -> float:
    """Parameters one token multiplies here: every operator, the dense FFNs,
    the routers, the head, and in an expert layer `num_experts_per_tok` x
    (held / published) experts."""
    return _outside_experts(cfg) + expert_layers(cfg) * (
        cfg["num_experts_per_tok"] * held_share(cfg) * expert_params(cfg))


def serve_token_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token that attends over `context` positions:
    scores and values of every query head in an attention layer; in a
    convolution layer the two gates and the taps' multiply-adds."""
    heads, d = cfg["num_attention_heads"], cfg["hidden_size"]
    attn = full_layers(cfg) * 2.0 * heads * 2 * _head_dim(cfg) * context
    conv = conv_layers(cfg) * (2.0 * cfg["conv_L_cache"] + 2) * d
    return 2.0 * matmul_params(cfg) + attn + conv


def kv_position_bytes(cfg, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one position holds in ONE attention layer."""
    return cfg["num_key_value_heads"] * 2 * _head_dim(cfg) * dtype_bytes


def slot_state_bytes(cfg, dtype_bytes: int = 2) -> int:
    """Bytes of state one slot holds over ALL convolution layers: the last
    `conv_L_cache - 1` inputs of each."""
    return (conv_layers(cfg) * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]
            * dtype_bytes)


def gqa_decode_call(cfg: dict, contexts, dtype_bytes: int = 2) -> dict:
    """One grouped-query paged decode-attention call of ONE attention layer:
    each live slot reads the K and V of its `context` positions once."""
    positions = sum(contexts)
    return {"flops": 2.0 * cfg["num_attention_heads"] * 2 * _head_dim(cfg) * positions,
            "bytes": positions * kv_position_bytes(cfg, dtype_bytes)}


def decode_step_bytes(cfg: dict, contexts, experts_touched: float,
                      dtype_bytes: int = 2) -> float:
    """The least a decode step reads: every weight outside the experts once
    (the embedding is read whole as the tied head), the taps, `experts_touched`
    experts' weights (summed over the expert layers), the live K/V of the
    attention layers, and each live slot's convolution state read and
    written back."""
    taps = conv_layers(cfg) * cfg["conv_L_cache"] * cfg["hidden_size"]
    weights = _outside_experts(cfg) + taps + experts_touched * expert_params(cfg)
    kv = sum(contexts) * full_layers(cfg) * kv_position_bytes(cfg, dtype_bytes)
    state = len(contexts) * 2 * slot_state_bytes(cfg, dtype_bytes)
    return weights * dtype_bytes + kv + state
