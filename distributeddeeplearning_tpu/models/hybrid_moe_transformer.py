"""A decoder whose layers are of several kinds, with sparse experts.

The pure-function model behind the paged serving engine for architectures
that mix **window** and **full** attention layers in one stack (each kind
with its own KV head count, rotary base and optional learned sink), use
grouped-query attention whose keys and values differ in width, rotate only
a leading part of every head, normalise with RMS norm, and run a gated FFN
that in most layers is a **mixture of experts** of which this chip holds a
stated subset.  A third kind of layer has no attention at all: a **gated
short convolution** (:func:`short_conv`), whose only memory of a sequence
is the last ``conv_taps - 1`` inputs of its depthwise convolution.  Values of
the spec also give an always-on **shared expert** beside the routed sum, a
sigmoid **gate on the attention's output**, a norm **after** each operator
as well as before it, layer kinds that **do not rotate** at all, and a
scaled embedding.

Everything about the architecture is in one hashable :class:`HybridSpec`;
parameters are a plain pytree with one dict per layer (no stacking: every
weight is a buffer of its own, so no step slices a stack of them).  There
is **one block function**, :func:`block`, which takes the layer's index and
the layer's *callback*: an attention layer's says how its cache is written
and read (``attention=``), a convolution layer's hands it the inputs just
before the rows it was given and keeps the newest for the next call
(``state=``), and a state-space layer's does that and also runs its scan from
the state it keeps (``state=``, a :class:`MambaState`).  The two forwards the
paged engine needs,
:func:`forward_prefill_chunk` and :func:`forward_decode`, and the
cache-free :func:`forward` differ only in the callbacks they hand it.

Numerics: weights and cache in the parameters' dtype (bfloat16 in
serving), every matmul accumulating in float32, the residual stream, the
norms, the softmax and the whole router in float32.

The cache is of two kinds under one pytree (``serve.kv_cache.
init_hybrid_cache``): full layers own a paged pool ``[pages, page_size,
kv_heads * width]`` (heads folded into the minor axis, so a bfloat16 page
tiles without padding) addressed through block tables; window layers own a
ring ``[slots, window, kv_heads * width]`` written at ``pos mod window`` and
masked by absolute position, so their bytes do not grow with the sequence.
A convolution layer owns ``[slots, (conv_taps - 1) * d]``: each slot's last
inputs, oldest first, folded into the minor axis like the KV heads.  That
state has no positions to mask by, so a chunk at offset 0 starts from zeros
whatever the slot holds.

A fourth kind, the **state-space** (Mamba) layer (:func:`mamba_mixer`),
carries more than a few inputs: beside its convolution's last ``d_conv - 1``
inputs, a state of ``d_state x d_inner`` values a slot that a selective scan
updates at every row (:mod:`ops.ssm_scan` over a chunk's rows, and one step
a live lane in a decode step).  Its cache is two per-slot leaves: the
convolution's last inputs, laid out as a convolution layer's, and the state
in float32, ``[slots, d_state, d_inner]``; both start from zeros at offset 0.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.ops import flash_decode as _fd
from distributeddeeplearning_tpu.ops.ssm_scan import (
    live_lanes,
    ssm_decode_step,
    ssm_scan_chunk,
)

PyTree = Any
FULL, WINDOW, CONV, MAMBA = 0, 1, 2, 3
DENSE, EXPERTS = 0, 1
#: what the expert layers count in a decode step (the order of the step's
#: small integer vector): pairs (token, expert) the router made over live
#: lanes, pairs that landed on held experts, the fullest held expert's
#: tokens and the held experts touched, each summed over the expert layers
EXPERT_COUNTS = ("pairs_total", "pairs_here", "tokens_max", "experts_touched")
#: a layer's norm scales (made 1, where every other weight is drawn)
NORM_SCALES = ("ln1", "ln2", "q_norm", "k_norm", "ln1_post", "ln2_post")
#: a state-space mixer's leaves that are made, not drawn (:func:`mamba_init`)
MAMBA_MADE = ("dt_norm", "b_norm", "c_norm", "A_log", "D", "b_dt")


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """A state-space (MAMBA) layer's numbers."""

    d_inner: int            # channels of the scan (``expand * d_model``)
    d_state: int            # states a channel
    d_conv: int             # taps of the causal depthwise convolution
    dt_rank: int            # width of the step's low-rank projection
    conv_bias: bool = True
    #: a learned RMS norm over each of the step, B and C before they are used
    inner_norms: bool = True

    def __post_init__(self):
        if min(self.d_inner, self.d_state, self.dt_rank) < 1 or self.d_conv < 2:
            raise ValueError(f"a state-space layer needs positive widths and "
                             f"d_conv of 2 or more, got {self}")


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """The architecture, as static numbers (hashable: a jit static)."""

    vocab_size: int
    d_model: int
    num_q_heads: int
    k_dim: int                      # width of a query/key head
    v_dim: int                      # width of a value head
    rotary_dim: int                 # leading dims of a head that rotate
    kv_heads_full: int
    kv_heads_window: int
    window: int
    theta_full: float
    theta_window: float
    sink_full: bool
    sink_window: bool
    value_scale: float
    eps: float
    attn_kinds: Tuple[int, ...]     # per layer: FULL, WINDOW, CONV or MAMBA
    ffn_kinds: Tuple[int, ...]      # per layer: DENSE or EXPERTS
    d_ff: int                       # dense FFN width
    d_expert: int                   # one expert's width
    num_experts: int                # the router's outputs
    experts_per_token: int
    experts_held: Tuple[int, ...]   # ids of the experts this chip holds
    norm_topk: bool = True
    routed_scale: float = 1.0
    #: added to the chosen scores' sum before it divides them
    topk_eps: float = 0.0
    #: taps of a CONV layer's causal depthwise convolution
    conv_taps: int = 0
    #: a learned RMS norm over every query and key head, before the rotation
    qk_norm: bool = False
    #: the head is the embedding, read transposed (no ``head`` leaf)
    tied_head: bool = False
    #: width of a gated FFN that every token takes beside its routed
    #: experts in an expert layer (0: none)
    shared_width: int = 0
    #: ``sigmoid(h Wgate)`` multiplies the attention's output, element by
    #: element, before the output projection
    output_gate: bool = False
    #: an RMS norm after each operator too, before it joins the residual
    post_norms: bool = False
    #: whether a full layer's queries and keys rotate at all (False: it has
    #: no position signal but the causal mask; a window layer always rotates)
    rotate_full: bool = True
    #: multiplies the embedding's rows as they enter the residual stream
    embed_scale: float = 1.0
    #: the state-space layers' numbers (where any layer is MAMBA)
    ssm: Optional[SsmSpec] = None

    def __post_init__(self):
        if len(self.attn_kinds) != len(self.ffn_kinds):
            raise ValueError("attn_kinds and ffn_kinds differ in length")
        if WINDOW in self.attn_kinds and self.window < 1:
            raise ValueError("window layers need a window of 1 or more")
        if CONV in self.attn_kinds and self.conv_taps < 2:
            raise ValueError("convolution layers need conv_taps of 2 or more")
        if MAMBA in self.attn_kinds and self.ssm is None:
            raise ValueError("state-space layers need an SsmSpec (ssm=)")
        for name, kv in (("full", self.kv_heads_full),
                         ("window", self.kv_heads_window)):
            if self.num_q_heads % kv:
                raise ValueError(
                    f"{self.num_q_heads} query heads not divisible by the "
                    f"{name} layers' {kv} KV heads"
                )
        if self.rotary_dim % 2 or self.rotary_dim > self.k_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} must be even "
                             f"and at most k_dim {self.k_dim}")
        if any(not 0 <= e < self.num_experts for e in self.experts_held) or (
                len(set(self.experts_held)) != len(self.experts_held)):
            raise ValueError("experts_held must be distinct ids below "
                             f"num_experts {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.attn_kinds)

    def kv_heads(self, kind: int) -> int:
        return self.kv_heads_window if kind == WINDOW else self.kv_heads_full

    def theta(self, kind: int) -> float:
        return self.theta_window if kind == WINDOW else self.theta_full

    def has_sink(self, kind: int) -> bool:
        return self.sink_window if kind == WINDOW else self.sink_full

    def rotates(self, kind: int) -> bool:
        return kind == WINDOW or self.rotate_full

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.attn_kinds) if k == kind)

    def index_in_kind(self, layer: int) -> int:
        """The layer's index among the layers of its attention kind (its
        place in that kind's cache)."""
        kind = self.attn_kinds[layer]
        return sum(1 for k in self.attn_kinds[:layer] if k == kind)


def spec_from_config(cfg: dict) -> HybridSpec:
    """A :class:`HybridSpec` from a configuration under its published keys
    (``hybrid_layer_pattern``, ``swa_num_key_value_heads``, ...); with
    ``layers_kept`` the layers run are those of the published patterns.
    ``n_routed_experts`` counts the experts held here; ``experts_held``
    their ids and ``n_routed_experts_published`` the router's width (both
    default to "all of them").  A configuration under the ``lfm2_moe`` keys
    (``layer_types``) is read by :func:`_spec_from_lfm2_moe`, one whose
    ``model_type`` is ``afmoe`` by :func:`_spec_from_afmoe`, ``jamba`` by
    :func:`_spec_from_jamba`."""
    if cfg.get("model_type") == "afmoe":
        return _spec_from_afmoe(cfg)
    if cfg.get("model_type") == "jamba":
        return _spec_from_jamba(cfg)
    if "layer_types" in cfg:
        return _spec_from_lfm2_moe(cfg)
    held = cfg.get("experts_held")
    n_router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    if held is None:
        held = list(range(cfg["n_routed_experts"]))
    layers = cfg["num_hidden_layers"]
    # a file may keep the published per-layer patterns whole and say which
    # of the published layers it runs
    kept = cfg.get("layers_kept", range(layers))
    return HybridSpec(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        num_q_heads=cfg["num_attention_heads"],
        k_dim=cfg["head_dim"],
        v_dim=cfg["v_head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        kv_heads_full=cfg["num_key_value_heads"],
        kv_heads_window=cfg["swa_num_key_value_heads"],
        window=cfg["sliding_window"],
        theta_full=float(cfg["rope_theta"]),
        theta_window=float(cfg["swa_rope_theta"]),
        sink_full=bool(cfg["add_full_attention_sink_bias"]),
        sink_window=bool(cfg["add_swa_attention_sink_bias"]),
        value_scale=float(cfg["attention_value_scale"]),
        eps=float(cfg["layernorm_epsilon"]),
        attn_kinds=tuple(cfg["hybrid_layer_pattern"][i] for i in kept),
        ffn_kinds=tuple(cfg["moe_layer_freq"][i] for i in kept),
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        num_experts=n_router,
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=tuple(held),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg.get("routed_scaling_factor") or 1.0),
    )


_LFM2_KINDS = {"conv": CONV, "full_attention": FULL}


def _spec_from_lfm2_moe(cfg: dict) -> HybridSpec:
    """The ``lfm2_moe`` keys: ``layer_types`` names every published layer
    ``conv`` (a gated short convolution of ``conv_L_cache`` taps) or
    ``full_attention`` (grouped-query, a learned RMS norm over every query
    and key head, rotary over the whole head); the first
    ``num_dense_layers`` published layers have a dense FFN and the others
    ``num_experts`` experts; the head is the embedding.  ``layers_kept``
    says which of the published layers are run (default: the first
    ``num_hidden_layers``); ``experts_held`` the experts held here
    (default: all of them)."""
    if cfg.get("conv_bias") or not cfg.get("use_expert_bias", True):
        raise ValueError("lfm2_moe: conv_bias true and use_expert_bias "
                         "false are not written")
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError("layers_kept and num_hidden_layers disagree")
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return HybridSpec(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        num_q_heads=heads,
        k_dim=head_dim,
        v_dim=head_dim,
        rotary_dim=head_dim,
        kv_heads_full=cfg["num_key_value_heads"],
        kv_heads_window=cfg["num_key_value_heads"],
        window=0,
        theta_full=float(cfg["rope_theta"]),
        theta_window=float(cfg["rope_theta"]),
        sink_full=False,
        sink_window=False,
        value_scale=1.0,
        eps=float(cfg["norm_eps"]),
        attn_kinds=tuple(_LFM2_KINDS[cfg["layer_types"][i]] for i in kept),
        ffn_kinds=tuple(DENSE if i < cfg["num_dense_layers"] else EXPERTS
                        for i in kept),
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg.get("experts_held",
                                   range(cfg["num_experts"]))),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg.get("routed_scaling_factor") or 1.0),
        topk_eps=1e-6,
        conv_taps=cfg["conv_L_cache"],
        qk_norm=True,
        tied_head=True,
    )


_AFMOE_KINDS = {"sliding_attention": WINDOW, "full_attention": FULL}


def _spec_from_afmoe(cfg: dict) -> HybridSpec:
    """The ``afmoe`` keys: ``layer_types`` names every published layer
    ``sliding_attention`` (a window of ``sliding_window``, rotary over the
    whole head) or ``full_attention`` (no position signal at all); both
    kinds have one KV head count, a learned RMS norm over every query and
    key head, a sigmoid gate on the attention's output and an RMS norm after
    each operator as well as before it; the first ``num_dense_layers``
    published layers have a dense FFN, the others ``num_shared_experts``
    always-on experts beside the routed ones (``route_norm``,
    ``route_scale``); the embedding is scaled by ``sqrt(hidden_size)``
    where ``mup_enabled``.  ``num_experts`` counts the experts held here,
    ``experts_held`` their ids and ``num_experts_published`` the router's
    width (both default to "all of them"); ``layers_kept`` says which of
    the published layers are run."""
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("afmoe: only the sigmoid router is written")
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError("layers_kept and num_hidden_layers disagree")
    d, head_dim = cfg["hidden_size"], cfg["head_dim"]
    return HybridSpec(
        vocab_size=cfg["vocab_size"],
        d_model=d,
        num_q_heads=cfg["num_attention_heads"],
        k_dim=head_dim,
        v_dim=head_dim,
        rotary_dim=head_dim,
        kv_heads_full=cfg["num_key_value_heads"],
        kv_heads_window=cfg["num_key_value_heads"],
        window=cfg["sliding_window"],
        theta_full=float(cfg["rope_theta"]),
        theta_window=float(cfg["rope_theta"]),
        sink_full=False,
        sink_window=False,
        value_scale=1.0,
        eps=float(cfg["rms_norm_eps"]),
        attn_kinds=tuple(_AFMOE_KINDS[cfg["layer_types"][i]] for i in kept),
        ffn_kinds=tuple(DENSE if i < cfg["num_dense_layers"] else EXPERTS
                        for i in kept),
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg.get("num_experts_published", cfg["num_experts"]),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg.get("experts_held",
                                   range(cfg["num_experts"]))),
        norm_topk=bool(cfg["route_norm"]),
        routed_scale=float(cfg.get("route_scale") or 1.0),
        topk_eps=1e-20,
        qk_norm=True,
        shared_width=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        output_gate=True,
        post_norms=True,
        rotate_full=False,
        embed_scale=float(d) ** 0.5 if cfg.get("mup_enabled") else 1.0,
    )


def _spec_from_jamba(cfg: dict) -> HybridSpec:
    """The ``jamba`` keys: layer ``i`` is attention where ``i mod
    attn_layer_period == attn_layer_offset`` and a state-space (Mamba) layer
    elsewhere, as the published modelling code derives it; attention has
    ``num_key_value_heads`` KV heads of ``hidden_size / num_attention_heads``
    and no position signal at all; every FFN is dense (``num_experts`` 1, so
    ``expert_layer_period`` routes nothing); the Mamba mixer has
    ``mamba_expand * hidden_size`` channels, ``mamba_d_state`` states a
    channel, ``mamba_d_conv`` taps (with a bias where ``mamba_conv_bias``)
    and a step of rank ``mamba_dt_rank``, and norms the step, B and C (the
    published code; no key says so)."""
    if cfg.get("num_experts", 1) != 1 or cfg.get("mamba_proj_bias"):
        raise ValueError("jamba: only num_experts 1 and mamba_proj_bias false "
                         "are written")
    layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return HybridSpec(
        vocab_size=cfg["vocab_size"],
        d_model=d,
        num_q_heads=heads,
        k_dim=d // heads,
        v_dim=d // heads,
        rotary_dim=0,
        kv_heads_full=kv,
        kv_heads_window=kv,
        window=0,
        theta_full=0.0,
        theta_window=0.0,
        sink_full=False,
        sink_window=False,
        value_scale=1.0,
        eps=float(cfg["rms_norm_eps"]),
        attn_kinds=tuple(FULL if i % period == offset else MAMBA
                         for i in range(layers)),
        ffn_kinds=(DENSE,) * layers,
        d_ff=cfg["intermediate_size"],
        d_expert=0,
        num_experts=1,
        experts_per_token=1,
        experts_held=(),
        tied_head=bool(cfg["tie_word_embeddings"]),
        rotate_full=False,
        ssm=SsmSpec(d_inner=cfg["mamba_expand"] * d,
                    d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
                    dt_rank=cfg["mamba_dt_rank"],
                    conv_bias=bool(cfg["mamba_conv_bias"])),
    )


def mamba_shapes(spec: HybridSpec) -> Dict[str, tuple]:
    """name -> shape of a state-space mixer's weights: ``w_in`` (the ``x``
    and ``z`` halves side by side), ``conv_w`` (a row a tap, oldest first),
    ``conv_b``, ``w_x`` (the step's, B's and C's columns side by side), the
    three inner norms, ``w_dt``, ``b_dt``, ``A_log`` (state-major: ``[d_state,
    d_inner]``), ``D`` and ``w_out``."""
    m, d = spec.ssm, spec.d_model
    di, n, r = m.d_inner, m.d_state, m.dt_rank
    out = {"w_in": (d, 2 * di), "conv_w": (m.d_conv, di)}
    if m.conv_bias:
        out["conv_b"] = (di,)
    out["w_x"] = (di, r + 2 * n)
    if m.inner_norms:
        out.update(dt_norm=(r,), b_norm=(n,), c_norm=(n,))
    out.update(w_dt=(r, di), b_dt=(di,), A_log=(n, di), D=(di,), w_out=(di, d))
    return out


def layer_shapes(spec: HybridSpec, layer: int) -> Dict[str, tuple]:
    """name -> shape of one layer's weights."""
    kind, d = spec.attn_kinds[layer], spec.d_model
    if kind == CONV:
        out = {"ln1": (d,), "w_in": (d, 3 * d), "conv_w": (spec.conv_taps, d),
               "w_out": (d, d), "ln2": (d,)}
    elif kind == MAMBA:
        out = {"ln1": (d,), **mamba_shapes(spec), "ln2": (d,)}
    else:
        hq, hkv = spec.num_q_heads, spec.kv_heads(kind)
        out = {
            "ln1": (d,), "wq": (d, hq * spec.k_dim),
            "wk": (d, hkv * spec.k_dim), "wv": (d, hkv * spec.v_dim),
            "wo": (hq * spec.v_dim, d), "ln2": (d,),
        }
        if spec.qk_norm:
            out.update(q_norm=(spec.k_dim,), k_norm=(spec.k_dim,))
        if spec.has_sink(kind):
            out["sink"] = (hq,)
        if spec.output_gate:
            out["w_gate"] = (d, hq * spec.v_dim)
    if spec.post_norms:
        out.update(ln1_post=(d,), ln2_post=(d,))
    if spec.ffn_kinds[layer] == DENSE:
        out.update(wg=(d, spec.d_ff), wu=(d, spec.d_ff), wd=(spec.d_ff, d))
    else:
        held, fe = len(spec.experts_held), spec.d_expert
        out.update(router=(d, spec.num_experts),
                   router_bias=(spec.num_experts,),
                   wg=(held, d, fe), wu=(held, d, fe), wd=(held, fe, d))
        if spec.shared_width:
            fs = spec.shared_width
            out.update(shared_wg=(d, fs), shared_wu=(d, fs),
                       shared_wd=(fs, d))
    return out


def mamba_init(key, name: str, shape: tuple, dtype):
    """A state-space mixer's leaves that are not plain normals
    (:data:`MAMBA_MADE`), made as the published Mamba code makes them: the
    inner norms' scales are 1, ``A_log`` row ``n`` is ``log(n + 1)`` (so ``A
    = -exp(A_log)`` runs -1 .. -d_state), ``D`` is 1, and ``b_dt`` is the
    inverse softplus of steps drawn log-uniform in [0.001, 0.1]."""
    if name in ("dt_norm", "b_norm", "c_norm", "D"):
        return jnp.ones(shape, dtype)
    if name == "A_log":
        n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def init_params(rng: jax.Array, spec: HybridSpec, *, dtype=jnp.float32,
                std: float = 0.02) -> PyTree:
    """Seeded weights: normal(0, std), norm scales 1, the sink logits and
    the router's correction bias small normals (so that a test can tell
    selecting by ``s + b`` from weighing by ``s``); a state-space mixer's
    :data:`MAMBA_MADE` leaves as :func:`mamba_init` makes them."""
    # a split of 16 a layer keeps the other kinds' seeded weights as they were
    per_layer = 20 if MAMBA in spec.attn_kinds else 16
    keys = iter(jax.random.split(rng, 2 + per_layer * spec.num_layers))

    def nrm(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std
                ).astype(dtype)

    layers = []
    for layer in range(spec.num_layers):
        p = {}
        for name, shape in layer_shapes(spec, layer).items():
            if spec.attn_kinds[layer] == MAMBA and name in MAMBA_MADE:
                p[name] = mamba_init(next(keys), name, shape, dtype)
            else:
                p[name] = (jnp.ones(shape, dtype) if name in NORM_SCALES
                           else nrm(shape))
        layers.append(p)
    params = {
        "embed": nrm((spec.vocab_size, spec.d_model)),
        "layers": layers,
        "final_norm": jnp.ones((spec.d_model,), dtype),
    }
    if not spec.tied_head:
        params["head"] = nrm((spec.d_model, spec.vocab_size))
    return params


# -- the block's parts ---------------------------------------------------------


def _mm(a, w):
    """``a @ w`` in the weights' dtype, accumulated in float32."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def rotary(x, positions, *, rotary_dim: int, theta: float):
    """Rotate the first ``rotary_dim`` dims of every head of ``x``
    [T, H, D] by its position (half-split layout: dim ``i`` pairs with
    ``i + rotary_dim/2``); the other dims pass through."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., rotary_dim:]], -1
    )


#: plain grouped-query attention with an optional sink (``ops.flash_decode``)
attend = _fd.gqa_attend


def gated_ffn(p, h, prefix: str = ""):
    """``(silu(h Wg) * h Wu) Wd`` at the dense width (``prefix`` "shared_":
    the always-on expert's three matrices)."""
    a = jax.nn.silu(_mm(h, p[prefix + "wg"])) * _mm(h, p[prefix + "wu"])
    return _mm(a, p[prefix + "wd"])


def route(p, h32, *, spec: HybridSpec):
    """The router over ALL experts, in float32: ``s = sigmoid(h Wr)``; the
    ``experts_per_token`` experts with the largest ``s + b`` are chosen
    (the correction bias selects and does not weigh); the weights are
    ``s[chosen]`` over their sum (plus ``topk_eps``).  Returns (ids [T, k],
    weights [T, k])."""
    s = jax.nn.sigmoid(jnp.dot(
        h32, p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(
        s + p["router_bias"].astype(jnp.float32), spec.experts_per_token)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if spec.norm_topk:
        total = w.sum(-1, keepdims=True)
        w = w / (total + spec.topk_eps if spec.topk_eps else total)
    return chosen, w * spec.routed_scale


def expert_layer(p, h32, *, spec: HybridSpec, live=None):
    """The share of a mixture-of-experts FFN that the experts held here
    give: dropless, static shapes.

    Every token is routed over all ``num_experts``; the pairs (token,
    expert) that land on a held expert are sorted by expert and run as
    three grouped matrix products (``jax.lax.ragged_dot`` over ``[held, d,
    width]`` weights); pairs for absent experts (and those of lanes that
    ``live`` [T] masks out) sort behind every group, so they take part in
    no product and add nothing.

    A grouped product costs its rows whether or not a group claims them,
    and of the ``T * k`` pairs only ``held / num_experts`` are expected
    here.  So the products run over the first ``rows`` sorted pairs, four
    times that expectation, when the pairs here fit in them, and over all
    ``T * k`` when they do not: nothing is ever dropped, and the cost
    follows the load.  Where the spec has a ``shared_width``, the always-on
    gated FFN of every row given (padding rows and dead lanes too: masking
    them would cost more than their rows of one product) is added to the
    routed sum; it takes part in no count.  Returns ``(y [T, d] float32,
    counts)`` with ``counts`` the :data:`EXPERT_COUNTS` of this layer (int32
    [4])."""
    T, d = h32.shape
    k, held = spec.experts_per_token, len(spec.experts_held)
    chosen, w = route(p, h32, spec=spec)
    local = np.full(spec.num_experts, held, np.int32)  # absent -> past the end
    local[list(spec.experts_held)] = np.arange(held, dtype=np.int32)
    group = jnp.asarray(local)[chosen]  # [T, k]
    if live is not None:
        group = jnp.where(live[:, None], group, held)
    group = group.reshape(T * k)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    here = sizes.sum()
    cdt = p["wg"].dtype
    x = h32.astype(cdt)
    weight = w.reshape(T * k)

    def products(rows):
        """The first ``rows`` sorted pairs through their experts, each
        weighted, added into its token's row of [T, d]."""
        first = order[:rows]
        xs = x[first // k]  # the pairs' tokens, grouped by expert
        a = jax.nn.silu(
            jax.lax.ragged_dot(xs, p["wg"], sizes,
                               preferred_element_type=jnp.float32)
        ) * jax.lax.ragged_dot(xs, p["wu"], sizes,
                               preferred_element_type=jnp.float32)
        y = jax.lax.ragged_dot(a.astype(cdt), p["wd"], sizes,
                               preferred_element_type=jnp.float32)
        # rows past the last group belong to no expert: whatever they hold, 0
        y = jnp.where((jnp.arange(rows) < here)[:, None],
                      y * weight[first, None], 0.0)
        return jnp.zeros((T, d), jnp.float32).at[first // k].add(y)

    expected = -(-T * k * held // spec.num_experts)
    few = min(T * k, -(-4 * expected // 8) * 8)
    if few < T * k:
        y = jax.lax.cond(here <= few, lambda: products(few),
                         lambda: products(T * k))
    else:
        y = products(T * k)
    if spec.shared_width:
        y = y + gated_ffn(p, h32, "shared_")
    made = jnp.int32(T * k) if live is None else live.sum().astype(jnp.int32) * k
    counts = jnp.stack(
        [made, here, sizes.max(), (sizes > 0).sum().astype(jnp.int32)])
    return y, counts


def attention_op(p, h, positions, *, spec: HybridSpec, kind: int, attention):
    """An attention layer's operator on the normed rows ``h`` [T, d]:
    projections, the per-head norm where the spec has one, the rotation
    where the kind rotates, the caller's ``attention`` (see :func:`block`),
    the output gate where the spec has one, the output projection."""
    T = h.shape[0]
    hq, hkv = spec.num_q_heads, spec.kv_heads(kind)
    cdt = p["wq"].dtype

    def rotated(w, heads, norm):
        y = _mm(h, p[w]).reshape(T, heads, spec.k_dim)
        if spec.qk_norm:
            y = rms_norm(y, p[norm], spec.eps)
        if not spec.rotates(kind):
            return y
        return rotary(y, positions, rotary_dim=spec.rotary_dim,
                      theta=spec.theta(kind))

    q, k = rotated("wq", hq, "q_norm"), rotated("wk", hkv, "k_norm")
    v = spec.value_scale * _mm(h, p["wv"]).reshape(T, hkv, spec.v_dim)
    ctx = attention(q.astype(cdt), k.astype(cdt), v.astype(cdt),
                    p["sink"] if spec.has_sink(kind) else None)
    ctx = ctx.reshape(T, hq * spec.v_dim)
    if spec.output_gate:
        ctx = ctx.astype(jnp.float32) * jax.nn.sigmoid(_mm(h, p["w_gate"]))
    return _mm(ctx, p["wo"])


def short_conv(p, h, *, spec: HybridSpec, state):
    """A convolution layer's operator on the normed rows ``h`` [T, d]:
    ``[B | C | X] = h W_in``; ``u = B * X``; ``c_t = sum_j w_j u_{t-taps+1+j}``
    (depthwise, causal, inputs before the sequence's start are 0); ``(C *
    c) W_out``.  No nonlinearity but the two gates.

    ``state(u [T, d]) -> (u_{t-taps+1}, ..., u_{t-1}, u_t)``, each [T, d],
    is the caller's: row ``t``'s earlier inputs come from wherever the
    caller keeps them (the rows above it, a slot's state), and the newest
    are left there for the next call.  ``u`` is rounded to the weights'
    dtype before it is summed or stored, so a sequence reads the same
    inputs whether they come from this call or an earlier one."""
    d = spec.d_model
    bcx = _mm(h, p["w_in"])
    u = (bcx[:, :d] * bcx[:, 2 * d:]).astype(p["w_in"].dtype)
    w = p["conv_w"].astype(jnp.float32)
    c = sum(w[j] * tap.astype(jnp.float32) for j, tap in enumerate(state(u)))
    return _mm(bcx[:, d:2 * d] * c, p["w_out"])


class MambaState(NamedTuple):
    """A state-space layer's callback, the caller's (:func:`mamba_mixer`).

    ``conv(x [T, di]) -> (x_{t-d_conv+1}, ..., x_t)``, each [T, di], as a
    convolution layer's ``state``: the inputs before the rows given come from
    wherever the caller keeps them, and the newest are left there.
    ``scan(dt, x, b, c, a, d) -> y [T, di]`` runs the recurrence over the
    rows given (``dt``, ``x`` [T, di], ``b``, ``c`` [T, d_state], ``a``
    [d_state, di], ``d`` [di], float32) from the state the caller keeps,
    and leaves the new state there."""

    conv: Callable
    scan: Callable


def mamba_mixer(p, h, *, spec: HybridSpec, state: MambaState):
    """A state-space (Mamba) layer's operator on the normed rows ``h`` [T,
    d]: ``[x | z] = h W_in``; ``x~ = silu(sum_j w_j x_{t-d_conv+1+j} + b)``
    (depthwise, causal, inputs before the sequence's start are 0); ``[dt | B
    | C] = x~ W_x``, each normed where the spec says so; ``dt =
    softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
    ``s_t = exp(dt_t A) s_{t-1} + (dt_t x~_t) B_t``, ``y_t = s_t C_t + D
    x~_t``; ``(y * silu(z)) W_out``.  ``x`` is rounded to the weights' dtype
    before it is summed or stored, as :func:`short_conv` rounds ``u``."""
    m = spec.ssm
    di, n, r = m.d_inner, m.d_state, m.dt_rank
    f32 = jnp.float32
    xz = _mm(h, p["w_in"])
    x = xz[:, :di].astype(p["w_in"].dtype)
    w = p["conv_w"].astype(f32)
    c = sum(w[j] * tap.astype(f32) for j, tap in enumerate(state.conv(x)))
    if m.conv_bias:
        c = c + p["conv_b"].astype(f32)
    xc = jax.nn.silu(c)
    dbc = _mm(xc, p["w_x"])
    dt, b, cc = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if m.inner_norms:
        dt = rms_norm(dt, p["dt_norm"], spec.eps)
        b = rms_norm(b, p["b_norm"], spec.eps)
        cc = rms_norm(cc, p["c_norm"], spec.eps)
    dt = jax.nn.softplus(_mm(dt, p["w_dt"]) + p["b_dt"].astype(f32))
    y = state.scan(dt, xc, b, cc, -jnp.exp(p["A_log"].astype(f32)),
                   p["D"].astype(f32))
    return _mm(y * jax.nn.silu(xz[:, di:]), p["w_out"])


def block(p, x, positions, *, spec: HybridSpec, layer: int, attention=None,
          state=None, live=None):
    """One layer on ``x`` [T, d] (float32 residual) at ``positions`` [T].

    An attention layer takes ``attention(q [T, Hq, dk], k [T, Hkv, dk], v
    [T, Hkv, dv], sink)`` -> ``ctx [T, Hq, dv]``, the caller's: it writes
    the layer's cache and reads what the queries may see.  A convolution
    layer takes ``state`` (:func:`short_conv`), a state-space layer a
    :class:`MambaState`.  Returns ``(x, counts)`` with ``counts`` the expert
    layer's (None in a dense layer)."""
    kind = spec.attn_kinds[layer]

    def joined(x, y, norm):
        """The operator's output into the residual, through the norm after
        it where the spec has one."""
        return x + (rms_norm(y, p[norm], spec.eps) if spec.post_norms else y)

    h = rms_norm(x, p["ln1"], spec.eps)
    if kind == CONV:
        y = short_conv(p, h, spec=spec, state=state)
    elif kind == MAMBA:
        y = mamba_mixer(p, h, spec=spec, state=state)
    else:
        y = attention_op(p, h, positions, spec=spec, kind=kind,
                         attention=attention)
    x = joined(x, y, "ln1_post")
    h = rms_norm(x, p["ln2"], spec.eps)
    if spec.ffn_kinds[layer] == DENSE:
        return joined(x, gated_ffn(p, h), "ln2_post"), None
    y, counts = expert_layer(p, h, spec=spec, live=live)
    return joined(x, y, "ln2_post"), counts


def _embed(params, tokens, spec):
    """The tokens' rows of the embedding as the float32 residual stream."""
    x = params["embed"][tokens].astype(jnp.float32)
    return x * spec.embed_scale if spec.embed_scale != 1.0 else x


def _logits(params, x, spec):
    h = rms_norm(x, params["final_norm"], spec.eps)
    if not spec.tied_head:
        return _mm(h, params["head"])
    # the embedding's own buffer, contracted over its minor axis
    embed = params["embed"]
    return jax.lax.dot_general(
        h.astype(embed.dtype), embed, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _stack(spec, params, x, positions, callback_of, live=None):
    """The layers in their published order; ``callback_of(layer)`` gives
    each its callback (an attention layer's ``attention``, a convolution or
    state-space layer's ``state``).  Returns (x, the expert layers' counts
    summed)."""
    total = jnp.zeros(len(EXPERT_COUNTS), jnp.int32)
    for layer, p in enumerate(params["layers"]):
        name = ("state" if spec.attn_kinds[layer] in (CONV, MAMBA)
                else "attention")
        x, counts = block(p, x, positions, spec=spec, layer=layer, live=live,
                          **{name: callback_of(layer)})
        if counts is not None:
            total = total + counts
    return x, total


# -- the whole forward (tests, the shares-add-up check) ---------------------------


def forward(params, tokens, *, spec: HybridSpec):
    """Next-token logits [s, vocab] of one sequence ``tokens`` [s], with
    no cache: every attention layer attends over the sequence itself, a
    convolution layer's earlier inputs are the rows above (zeros before
    the sequence's start), and a state-space layer scans from a zero
    state."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    if WINDOW in spec.attn_kinds:
        in_window = pos[None, :] > pos[:, None] - spec.window

    def rows_above(taps):
        return lambda u: tuple(jnp.pad(u, ((back, 0), (0, 0)))[:s]
                               for back in range(taps - 1, -1, -1))

    def scan_from_zero(dt, x, b, c, a, d):
        return ssm_scan_chunk(dt, x, b, c, a, d, jnp.zeros_like(a))[0]

    def callback_of(layer):
        kind = spec.attn_kinds[layer]
        if kind == CONV:
            return rows_above(spec.conv_taps)
        if kind == MAMBA:
            return MambaState(rows_above(spec.ssm.d_conv), scan_from_zero)
        visible = causal & in_window if kind == WINDOW else causal
        return lambda q, k, v, sink: attend(q, k, v, visible, sink)

    x = _embed(params, tokens, spec)
    x, _ = _stack(spec, params, x, pos, callback_of)
    return _logits(params, x, spec)


# -- the two forwards of the paged engine ------------------------------------------


def ring_positions(last, window: int):
    """The absolute position each ring index holds when the newest written
    position is ``last`` (scalar or [B]): index ``r`` holds the largest
    ``p <= last`` with ``p mod window == r``; negative = never written."""
    last = jnp.asarray(last)[..., None]
    r = jnp.arange(window)
    return last - jnp.mod(last - r, window)


def forward_decode(params, token, cache, pos, block_tables, live, *,
                   spec: HybridSpec, page_size: int, kernel: str = "gather"):
    """One token for every slot: ``token``/``pos`` [B], ``block_tables``
    [B, nb] (full layers' pages), ``live`` [B] bool (a lane that is not
    live writes to the scratch page and leaves its ring, its convolution
    state and its scan's state as they were, and its token is routed to no
    expert).  A state-space layer takes one step of its recurrence for
    each live lane (:func:`ops.ssm_scan.ssm_decode_step`, which reads and
    writes no other lane's state).
    Returns ``(logits [B, vocab] float32,
    cache, counts)`` with ``counts`` the :data:`EXPERT_COUNTS` over the
    step's expert layers (int32 [4])."""
    B = token.shape[0]
    W = spec.window
    rows = jnp.arange(B)
    page = block_tables[rows, pos // page_size]
    off = pos % page_size
    if WINDOW in spec.attn_kinds:
        slot_pos = ring_positions(pos, W)  # [B, W]
    if MAMBA in spec.attn_kinds:
        lanes = live_lanes(live)  # the live lanes first, and how many
    cache = {name: list(leaves) for name, leaves in cache.items()}

    def callback_of(layer):
        kind, i = spec.attn_kinds[layer], spec.index_in_kind(layer)

        def conv(u, leaf="conv_state", taps=spec.conv_taps):
            # each lane's earlier inputs are its slot's state, which then
            # moves on by one (a lane that is not live keeps its own)
            held = cache[leaf][i]
            d = u.shape[1]
            u = u.astype(held.dtype)
            moved = jnp.concatenate([held[:, d:], u], axis=1)
            cache[leaf][i] = jnp.where(live[:, None], moved, held)
            return tuple(held[:, j * d:(j + 1) * d]
                         for j in range(taps - 1)) + (u,)

        def scan(dt, x, b, c, a, d):
            # one step for each live lane from its slot's state [B, d_state,
            # d_inner], in place: the other lanes' are neither read nor written
            y, cache["ssm_state"][i] = ssm_decode_step(
                dt, x, b, c, a, d, cache["ssm_state"][i], lanes)
            return y

        if kind == CONV:
            return conv
        if kind == MAMBA:
            return MambaState(
                lambda x: conv(x, "mamba_conv_state", spec.ssm.d_conv), scan)

        def full(q, k, v, sink):
            k_pool = cache["k_full"][i].at[page, off].set(k.reshape(B, -1))
            v_pool = cache["v_full"][i].at[page, off].set(v.reshape(B, -1))
            cache["k_full"][i], cache["v_full"][i] = k_pool, v_pool
            return _fd.decode_attention_gqa_paged(
                q, k_pool, v_pool, pos, block_tables, page_size=page_size,
                kernel=kernel, sink=sink)

        def window(q, k, v, sink):
            hkv = k.shape[1]
            r = pos % W
            rings = []
            for name, new in (("k_win", k), ("v_win", v)):
                ring = cache[name][i]
                new = jnp.where(live[:, None], new.reshape(B, -1), ring[rows, r])
                cache[name][i] = ring = ring.at[rows, r].set(new)
                rings.append(ring.reshape(B, W, hkv, -1))
            return jax.vmap(
                lambda q1, k1, v1, p1: attend(
                    q1[None], k1, v1, (p1 >= 0)[None], sink)[0]
            )(q, *rings, slot_pos)

        return window if kind == WINDOW else full

    x = _embed(params, token, spec)
    x, counts = _stack(spec, params, x, pos, callback_of, live=live)
    cache = {name: tuple(leaves) for name, leaves in cache.items()}
    return _logits(params, x, spec), cache, counts


def forward_prefill_chunk(params, tokens, cache, block_table, offset, slot,
                          real, *, spec: HybridSpec, page_size: int,
                          kernel: str = "gather"):
    """One chunk of one sequence's prompt: ``tokens`` [1, C] at positions
    ``[offset, offset + C)`` of which the first ``real`` are the prompt's
    (the rest pad the chunk to a compiled width), in ``slot``.

    A full layer writes the chunk's K/V into its pages (``block_table``
    [nb]) and attends over the pages a few at a time, up to the chunk's
    end, so its cost follows the live context.  A window layer
    attends to the ring's positions and to the chunk itself, then leaves the
    chunk's last ``window`` real positions in the ring.  A convolution
    layer starts from the slot's state (from zeros where ``offset`` is 0:
    a sequence's first chunk, whatever the slot's last occupant left) and
    leaves the inputs of the chunk's last ``conv_taps - 1`` real positions
    there.  A state-space layer does the same with its convolution's inputs
    and scans the chunk's rows from the slot's state (zeros at offset 0)
    with :func:`ops.ssm_scan.ssm_scan_chunk`; the rows that pad the chunk
    take a step of 0, which leaves the state as it was, so what is stored is
    the state after the last real row.  Returns ``(logits [1, 1, vocab] of
    the last real position, cache)``: the one row a serving engine samples
    from."""
    b, C = tokens.shape
    if b != 1:
        raise ValueError(f"chunked prefill is per-sequence, got batch {b}")
    W = spec.window
    nb = block_table.shape[0]
    posns = offset + jnp.arange(C)
    page_idx = posns // page_size
    pages = jnp.where(page_idx < nb,
                      block_table[jnp.minimum(page_idx, nb - 1)], 0)
    offs = posns % page_size
    if WINDOW in spec.attn_kinds:
        held_pos = ring_positions(offset - 1, W)  # [W] what the ring holds now
        # the chunk's last W real positions go into the ring
        tail = real - W + jnp.arange(W)
        tail_ok = tail >= 0
        tail_src = jnp.maximum(tail, 0)
        tail_dst = jnp.mod(offset + tail, W)
        in_chunk = (posns[None, :] <= posns[:, None]) & (
            posns[None, :] > posns[:, None] - W)
        on_ring = (held_pos[None, :] >= 0) & (
            held_pos[None, :] > posns[:, None] - W)
    cache = {name: list(leaves) for name, leaves in cache.items()}

    def callback_of(layer):
        kind, i = spec.attn_kinds[layer], spec.index_in_kind(layer)

        def conv(u, name="conv_state", taps=spec.conv_taps):
            leaf = cache[name][i]
            before = taps - 1
            u = u.astype(leaf.dtype)
            held = jnp.where(offset > 0, leaf[slot], 0).reshape(before, -1)
            rows = jnp.concatenate([held, u], axis=0)  # row r: chunk row r - before
            # the last real rows (with fewer than `before` of them, what
            # was held moves up) stay for the next chunk or decode step
            cache[name][i] = leaf.at[slot].set(
                jax.lax.dynamic_slice_in_dim(rows, real, before).reshape(-1))
            return tuple(rows[j:j + C] for j in range(taps))

        def scan(dt, x, b, c, a, d):
            leaf = cache["ssm_state"][i]
            s0 = jnp.where(offset > 0, leaf[slot], 0)
            dt = jnp.where((jnp.arange(C) < real)[:, None], dt, 0.0)
            y, s = ssm_scan_chunk(dt, x, b, c, a, d, s0)
            cache["ssm_state"][i] = leaf.at[slot].set(s)
            return y

        if kind == CONV:
            return conv
        if kind == MAMBA:
            return MambaState(
                lambda x: conv(x, "mamba_conv_state", spec.ssm.d_conv), scan)

        def full(q, k, v, sink):
            k_pool = cache["k_full"][i].at[pages, offs].set(k.reshape(C, -1))
            v_pool = cache["v_full"][i].at[pages, offs].set(v.reshape(C, -1))
            cache["k_full"][i], cache["v_full"][i] = k_pool, v_pool
            return _fd.chunk_attention_gqa_paged(
                q, k_pool, v_pool, block_table, posns, page_size=page_size,
                sink=sink)

        def window(q, k, v, sink):
            hkv = k.shape[1]
            k_ring, v_ring = cache["k_win"][i], cache["v_win"][i]
            keys = jnp.concatenate(
                [k_ring[slot].reshape(W, hkv, -1), k], axis=0)
            vals = jnp.concatenate(
                [v_ring[slot].reshape(W, hkv, -1), v], axis=0)
            ctx = attend(q, keys, vals,
                         jnp.concatenate([on_ring, in_chunk], axis=1), sink)
            for name, ring, new in (("k_win", k_ring, k), ("v_win", v_ring, v)):
                rows = jnp.where(tail_ok[:, None],
                                 new.reshape(C, -1)[tail_src],
                                 ring[slot, tail_dst])
                cache[name][i] = ring.at[slot, tail_dst].set(rows)
            return ctx

        return window if kind == WINDOW else full

    x = _embed(params, tokens[0], spec)
    # the rows that pad the chunk reach no expert
    x, _ = _stack(spec, params, x, posns, callback_of,
                  live=jnp.arange(C) < real)
    x = jax.lax.dynamic_slice_in_dim(x, real - 1, 1, axis=0)
    cache = {name: tuple(leaves) for name, leaves in cache.items()}
    return _logits(params, x, spec)[None], cache
