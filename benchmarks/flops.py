"""Operations and bytes the algorithm needs, computed from shapes.

Kept with the benchmark so that every PR counts the same work the same way.
Embedding look-ups are not matmuls; recomputed work is never counted.
"""


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul per token: the blocks' four
    projections and the output head (the embedding is a look-up)."""
    d, ff = cfg["hidden_size"], cfg["ffn_dim"]
    per_layer = 4 * d * d + 2 * d * ff  # qkv 3d^2 + proj d^2 + two FF mats
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def serve_token_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token that attends over `context` positions."""
    attn = 4 * cfg["num_hidden_layers"] * cfg["hidden_size"] * context
    return 2.0 * matmul_params(cfg) + attn


def train_token_flops(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token at `seq_len`, causal
    attention: 6 x matmul parameters, plus QK^T and PV over the visible half
    of the square (2 matmuls x 2 FLOPs x s/2 x d forward, x3 with backward)."""
    attn = 6 * cfg["num_hidden_layers"] * seq_len * cfg["hidden_size"]
    return 6.0 * matmul_params(cfg) + attn


def flash_decode_call(cfg: dict, contexts, dtype_bytes: int = 4) -> dict:
    """One paged decode-attention call of ONE layer: each live slot reads the
    K and V of its `context` positions once, and one query/output row."""
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    positions = sum(contexts)
    kv_bytes = 2 * positions * h * hd * dtype_bytes
    qo_bytes = 2 * len(contexts) * h * hd * 4
    return {"flops": 4.0 * positions * h * hd, "bytes": kv_bytes + qo_bytes}


#: kernel -> (matmuls counted, tensors read and written) of one layer's causal
#: attention. Forward: QK^T and PV over the visible half; reads q, k, v, writes
#: o. The dQ kernel recomputes S (not counted) and forms dP, dQ; reads q, k, v,
#: dO, writes dQ. The dKV kernel likewise forms dV, dK; writes two tensors. The
#: row statistics are small and left out.
FLASH_ATTENTION_KERNELS = {"fwd": (2, 4), "bwd_dq": (2, 5), "bwd_dkv": (2, 6)}


def flash_attention_call(cfg: dict, batch: int, seq_len: int, kernel: str,
                         dtype_bytes: int = 2) -> dict:
    """One call of one flash-attention kernel: the causal attention of ONE
    layer over `batch` sequences."""
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    matmuls, tensors = FLASH_ATTENTION_KERNELS[kernel]
    half_square = batch * h * seq_len * seq_len / 2
    return {"flops": matmuls * 2.0 * half_square * hd,
            "bytes": tensors * batch * seq_len * h * hd * dtype_bytes}


def roofline_least_seconds(work: dict, peaks: dict, flops_key: str = "bf16_flops"):
    """Least time the chip could take, and which of the two bounds it."""
    t_compute = work["flops"] / peaks[flops_key]
    t_memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
