"""Operations and bytes of the `afmoe` family, computed from shapes: what one
token multiplies HERE (this chip's share of the stated deployment), and the
least bytes a decode step and its grouped-query decode kernel have to read."""
from afmoe_weights import experts_held, layers_of, router_width, shared_width


def attention_params(cfg) -> int:
    """Wq, Wo and the output gate at the query heads' width, Wk and Wv at the
    KV heads'."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * d * heads * dh + 2 * d * kv * dh


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * shared_width(cfg)


def held_share(cfg) -> float:
    """Of a token's chosen experts, the expected share held here."""
    return experts_held(cfg) / router_width(cfg)


def _outside_routed(cfg) -> int:
    """Every weight a token multiplies outside the routed experts: attention
    with its gate, the dense FFNs, the routers, the shared experts and the
    head (the embedding is a look-up of a few rows)."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for _, dense in layers_of(cfg):
        total += attention_params(cfg)
        total += (3 * d * cfg["intermediate_size"] if dense
                  else d * router_width(cfg) + shared_params(cfg))
    return total


def expert_layers(cfg) -> int:
    return sum(1 for _, dense in layers_of(cfg) if not dense)


def full_layers(cfg) -> int:
    return sum(1 for sliding, _ in layers_of(cfg) if not sliding)


def window_layers(cfg) -> int:
    return sum(1 for sliding, _ in layers_of(cfg) if sliding)


def held_params(cfg) -> int:
    """Every parameter this chip holds (the norms' scales left out)."""
    return (_outside_routed(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
            + expert_layers(cfg) * experts_held(cfg) * expert_params(cfg))


def matmul_params(cfg: dict) -> float:
    """Parameters one token multiplies here: attention and its gate, the dense
    FFN, the router over all experts, the shared expert, the head over this
    slice of the vocabulary, and in an expert layer `num_experts_per_tok` x
    (held / published) routed experts."""
    return _outside_routed(cfg) + expert_layers(cfg) * (
        cfg["num_experts_per_tok"] * held_share(cfg) * expert_params(cfg))


def serve_token_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token that attends over `context` positions:
    scores and values of every query head, a window layer's context capped at
    the window."""
    heads, dh = cfg["num_attention_heads"], cfg["head_dim"]
    seen = (full_layers(cfg) * context
            + window_layers(cfg) * min(context, cfg["sliding_window"]))
    return 2.0 * matmul_params(cfg) + 2.0 * heads * 2 * dh * seen


def kv_position_bytes(cfg, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one position holds in ONE attention layer."""
    return cfg["num_key_value_heads"] * 2 * cfg["head_dim"] * dtype_bytes


def gqa_decode_call(cfg: dict, contexts, dtype_bytes: int = 2) -> dict:
    """One grouped-query paged decode-attention call of ONE full layer: each
    live slot reads the K and V of its `context` positions once."""
    positions = sum(contexts)
    return {"flops": 2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * positions,
            "bytes": positions * kv_position_bytes(cfg, dtype_bytes)}


def decode_step_bytes(cfg: dict, contexts, experts_touched: float,
                      dtype_bytes: int = 2) -> float:
    """The least a decode step reads: every weight outside the routed experts
    once (the shared expert among them), `experts_touched` routed experts'
    weights (summed over the expert layers), and the LIVE K/V of both kinds of
    layer: a full layer's `context` positions, a window layer's at most
    `sliding_window` of them, whatever the ring's capacity."""
    weights = _outside_routed(cfg) + experts_touched * expert_params(cfg)
    seen = sum(full_layers(cfg) * c
               + window_layers(cfg) * min(c, cfg["sliding_window"])
               for c in contexts)
    return weights * dtype_bytes + seen * kv_position_bytes(cfg, dtype_bytes)
