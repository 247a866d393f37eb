"""Operations and bytes of the `mimo_v2` family, computed from shapes: what one
token multiplies HERE (this chip's share of the stated deployment), and the
least bytes a decode step and its grouped-query decode kernel have to read."""


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return cfg["hybrid_layer_pattern"][:n], cfg["moe_layer_freq"][:n]


def _kv_heads(cfg, window: bool) -> int:
    return cfg["swa_num_key_value_heads"] if window else cfg["num_key_value_heads"]


def attention_params(cfg, window: bool) -> int:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv, kv = cfg["head_dim"], cfg["v_head_dim"], _kv_heads(cfg, window)
    return d * heads * dk + d * kv * (dk + dv) + heads * dv * d


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg) -> float:
    """Of a token's chosen experts, the expected share held here."""
    published = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    return cfg["n_routed_experts"] / published


def matmul_params(cfg: dict) -> float:
    """Parameters one token multiplies here: attention, the dense FFN, the
    router over all experts, the head over this slice of the vocabulary, and
    in an expert layer `num_experts_per_tok` x (held / published) experts."""
    d = cfg["hidden_size"]
    router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    total = d * cfg["vocab_size"]
    for kind, moe in zip(*_layers(cfg)):
        total += attention_params(cfg, kind == 1)
        if moe:
            total += d * router + (cfg["num_experts_per_tok"] * held_share(cfg)
                                   * expert_params(cfg))
        else:
            total += 3 * d * cfg["intermediate_size"]
    return total


def serve_token_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token that attends over `context` positions:
    scores and values of every query head, a window layer's context capped
    at the window."""
    heads, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    attn = 0.0
    for kind, _ in zip(*_layers(cfg)):
        seen = min(context, cfg["sliding_window"]) if kind == 1 else context
        attn += 2.0 * heads * (dk + dv) * seen
    return 2.0 * matmul_params(cfg) + attn


def kv_position_bytes(cfg, window: bool, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one position holds in ONE layer of the kind."""
    return _kv_heads(cfg, window) * (cfg["head_dim"] + cfg["v_head_dim"]) * dtype_bytes


def gqa_decode_call(cfg: dict, contexts, dtype_bytes: int = 2) -> dict:
    """One grouped-query paged decode-attention call of ONE full layer: each
    live slot reads the K and V of its `context` positions once."""
    heads, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    positions = sum(contexts)
    return {"flops": 2.0 * heads * (dk + dv) * positions,
            "bytes": positions * kv_position_bytes(cfg, False, dtype_bytes)}


def decode_step_bytes(cfg: dict, contexts, experts_touched: float,
                      dtype_bytes: int = 2) -> float:
    """The least a decode step reads: every weight outside the experts once
    (the embedding is a look-up of a few rows), `experts_touched` experts'
    weights (summed over the expert layers), and the live K/V of both kinds
    of layer."""
    d = cfg["hidden_size"]
    router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    weights = d * cfg["vocab_size"]
    kv = 0
    for kind, moe in zip(*_layers(cfg)):
        weights += attention_params(cfg, kind == 1)
        weights += d * router if moe else 3 * d * cfg["intermediate_size"]
        for c in contexts:
            seen = min(c, cfg["sliding_window"]) if kind == 1 else c
            kv += seen * kv_position_bytes(cfg, kind == 1, dtype_bytes)
    return (weights + experts_touched * expert_params(cfg)) * dtype_bytes + kv
