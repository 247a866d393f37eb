"""What the paged engine needs to know of a model, in one small description.

``PagedInferenceEngine`` used to import one model's two forwards and take
one ``num_heads``.  It now takes a :class:`ServedModel`: the model's two
forwards (one prefill chunk, one decode step), how its cache is laid out
and scrubbed, the names its programs carry in a trace, and what the engine
has to refuse for it.  Two bindings exist:

- :func:`opt_model` binds ``models.pipelined_transformer`` (the OPT block:
  one head count, one page pool ``[pages, L, page_size, h, hd]``); the
  engine's programs, shapes and numbers for it are what they were.
- :func:`hybrid_model` binds ``models.hybrid_moe_transformer`` (window and
  full attention layers with their own KV head counts and cache lifetimes,
  sparse experts of which a stated subset is held, with or without an
  always-on one beside them; gated short-convolution layers with no K/V at
  all; state-space layers): its cache has per-slot state (the window
  layers' rings, the convolution layers' last inputs, the state-space
  layers' last inputs and scan state) beside the pages, so
  its chunk program is also told the slot and how many of the chunk's
  tokens are real, its decode program which lanes are live, and its decode
  step returns a small vector of expert counts that rides with the step's
  one fetch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet, Optional

import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.serve import kv_cache

#: what an engine may be asked for and a model may refuse
FEATURES = {
    "prefix_cache": "sharing prompt pages across requests",
    "int8_pool": "the int8 KV pool",
    "host_tier": "the host page tier",
    "verify": "speculative verify",
    "tensor_mesh": "a tensor-parallel mesh",
}


class Refused(ValueError):
    """The engine was asked for something this model cannot serve yet."""


@dataclasses.dataclass(frozen=True)
class ServedModel:
    family: str
    vocab_size: int
    num_layers: int
    #: the longest sequence the model can place (None: positions are
    #: computed, not looked up)
    max_positions: Optional[int]
    #: ``(num_pages, page_size, batch_slots, dtype) -> cache``
    init_cache: Callable[..., Any]
    #: ``(params, tokens [1, C], cache, table [nb], offset, *slot_args, page_size,
    #: kernel, mesh) -> (logits, cache)``; ``slot_args`` = ``(slot, real)``
    #: where the cache holds per-slot state
    prefill_chunk: Callable[..., Any]
    #: ``(params, token [B], cache, pos [B], tables [B, nb], *slot_args,
    #: page_size, kernel, mesh) -> (logits, cache[, counts])``;
    #: ``slot_args`` = ``(live [B],)`` where the cache holds per-slot state
    decode: Callable[..., Any]
    #: ``(cache, page_ids [nb], from_offs [nb], *slot_args, page_size) ->
    #: cache``: zero the listed pages from the given offsets on (and the
    #: slot's own state, where there is any)
    scrub: Callable[..., Any]
    #: ``(cache, page, off) -> cache``: NaN into one stored key (fault tests)
    poison: Callable[..., Any]
    #: the names of the engine's two jitted functions for this model (a
    #: trace shows them as ``jit_<name>``)
    programs: Dict[str, str] = dataclasses.field(default_factory=lambda: {
        "decode": "_decode_fn", "prefill_chunk": "_chunk_fn"})
    #: the cache holds state per slot beside the pages
    slot_state: bool = False
    #: the chunk program returns the last real position's logits only
    last_logits_only: bool = False
    #: the narrowest chunk worth a program of its own (a prompt's remainder
    #: is padded up to a power-of-two multiple of it)
    chunk_floor: int = 8
    #: ``(counts, live_pos) -> {ServeReport field: this step's addend}``,
    #: on the host: ``counts`` is what the decode step returned beside its
    #: logits, ``live_pos`` the live lanes' positions.  None: the step
    #: counts nothing.  The engine adds what comes back and reads none of it
    count_step: Optional[Callable[..., Dict[str, float]]] = None
    refuses: FrozenSet[str] = frozenset()
    #: feature -> the model's own reason, said before the caller's
    reasons: Dict[str, str] = dataclasses.field(default_factory=dict)

    def refuse(self, feature: str, why: str = "") -> None:
        """Raise :class:`Refused`, by name, if this model cannot run with
        ``feature`` (a key of :data:`FEATURES`)."""
        if feature in self.refuses:
            said = "; ".join(x for x in (self.reasons.get(feature), why) if x)
            raise Refused(
                f"the {self.family!r} model refuses {feature} "
                f"({FEATURES[feature]})" + (f": {said}" if said else "")
            )


# -- the OPT block --------------------------------------------------------------


def opt_model(params, *, num_heads: int) -> ServedModel:
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward_decode_paged,
        forward_prefill_chunk,
    )

    d_model = params["embed"].shape[1]
    num_layers = params["blocks"]["qkv"].shape[0]

    def init_cache(*, num_pages, page_size, batch_slots, dtype):
        return kv_cache.init_paged_cache(
            num_pages=num_pages, num_layers=num_layers, page_size=page_size,
            num_heads=num_heads, head_dim=d_model // num_heads, dtype=dtype,
        )

    def prefill_chunk(params, tokens, cache, table, offset, **kw):
        return forward_prefill_chunk(
            params, tokens, cache, table, offset, num_heads=num_heads, **kw)

    def decode(params, token, cache, pos, tables, **kw):
        return forward_decode_paged(
            params, token, cache, pos, tables, num_heads=num_heads, **kw)

    def scrub(cache, page_ids, from_offs, *, page_size):
        # zero offsets >= from_offs[i] of page page_ids[i], every
        # leaf; untouched lanes point at the scratch page with
        # from_offs = page_size (an empty mask) so one compiled
        # program covers every (slot, from_pos) combination
        zero = (
            jnp.arange(page_size)[None, :] >= from_offs[:, None]
        )  # [nb, ps]
        out = {}
        for key, leaf in cache.items():
            rows = leaf[page_ids]  # [nb, L, ps, ...]
            m = zero.reshape(
                (zero.shape[0], 1, page_size)
                + (1,) * (rows.ndim - 3)
            )
            out[key] = leaf.at[page_ids].set(
                jnp.where(m, jnp.zeros((), leaf.dtype), rows)
            )
        return out

    def poison(cache, page, off):
        c = dict(cache)
        # int8 K can't hold NaN — poison the f32 scales
        name = "k_scale" if "k_scale" in c else "k"
        c[name] = c[name].at[page, :, off].set(jnp.nan)
        return c

    return ServedModel(
        family="opt",
        vocab_size=params["head"].shape[1],
        num_layers=num_layers,
        max_positions=params["pos"].shape[0],
        init_cache=init_cache,
        prefill_chunk=prefill_chunk,
        decode=decode,
        scrub=scrub,
        poison=poison,
    )


# -- window and full attention layers mixed, sparse experts ------------------------


def hybrid_model(spec) -> ServedModel:
    """The binding of ``models.hybrid_moe_transformer`` for ``spec`` (a
    :class:`~..models.hybrid_moe_transformer.HybridSpec`)."""
    from distributeddeeplearning_tpu.models import hybrid_moe_transformer as hm

    n_full = len(spec.layers_of(hm.FULL))
    n_window = len(spec.layers_of(hm.WINDOW))
    n_conv = len(spec.layers_of(hm.CONV))
    n_mamba = len(spec.layers_of(hm.MAMBA))
    ssm = {} if spec.ssm is None else dict(
        ssm_conv_positions=spec.ssm.d_conv - 1, d_inner=spec.ssm.d_inner,
        d_state=spec.ssm.d_state)

    # bytes of the cache this description laid out (set by init_cache, read
    # by count_step): the per-slot state of ONE lane, the K/V of ONE position
    laid_out = {"lane_state": 0, "position": 0}

    def init_cache(*, num_pages, page_size, batch_slots, dtype):
        cache = kv_cache.init_hybrid_cache(
            num_pages=num_pages, page_size=page_size,
            batch_slots=batch_slots, window=spec.window,
            full_layers=n_full, window_layers=n_window,
            kv_heads_full=spec.kv_heads_full,
            kv_heads_window=spec.kv_heads_window,
            k_dim=spec.k_dim, v_dim=spec.v_dim,
            conv_layers=n_conv, conv_positions=max(spec.conv_taps - 1, 0),
            d_model=spec.d_model, ssm_layers=n_mamba, **ssm, dtype=dtype,
        )
        laid_out["lane_state"] = kv_cache.slot_state_bytes(cache) // batch_slots
        laid_out["position"] = kv_cache.page_bytes(cache) // page_size
        return cache

    def prefill_chunk(params, tokens, cache, table, offset, slot, real, *,
                      page_size, kernel, mesh=None):
        return hm.forward_prefill_chunk(
            params, tokens, cache, table, offset, slot, real, spec=spec,
            page_size=page_size, kernel=kernel)

    def decode(params, token, cache, pos, tables, live, *, page_size, kernel,
               mesh=None):
        return hm.forward_decode(
            params, token, cache, pos, tables, live, spec=spec,
            page_size=page_size, kernel=kernel)

    def scrub(cache, page_ids, from_offs, slot, *, page_size):
        # the listed pages from their offsets on, in every full layer, and
        # the slot's whole ring in every window layer and whole state in
        # every convolution and state-space layer: what they hold is the
        # sequence's newest positions, which a scrub from any position reaches
        zero = (jnp.arange(page_size)[None, :] >= from_offs[:, None])[..., None]
        out = {}
        for name, leaves in cache.items():
            if name in kv_cache.RING_LEAVES:
                out[name] = tuple(
                    leaf.at[slot].set(jnp.zeros((), leaf.dtype))
                    for leaf in leaves)
            else:
                out[name] = tuple(
                    leaf.at[page_ids].set(jnp.where(
                        zero, jnp.zeros((), leaf.dtype), leaf[page_ids]))
                    for leaf in leaves)
        return out

    def poison(cache, page, off):
        c = dict(cache)
        first, *rest = c["k_full"]
        c["k_full"] = (first.at[page, off].set(jnp.nan), *rest)
        return c

    expert_layers = max(sum(k == hm.EXPERTS for k in spec.ffn_kinds), 1)
    held_experts = max(len(spec.experts_held), 1)

    def count_step(counts, live_pos):
        # the ``*_sum`` entries are sums over decode steps of the step's
        # mean over its expert layers; a full layer holds every position
        # of a live slot, a window layer at most the window; a live lane
        # holds its per-slot state whole and its positions' K/V in pages
        step = dict(zip(hm.EXPERT_COUNTS, (int(x) for x in counts)))
        held = live_pos.astype(np.int64) + 1
        return {
            "expert_pairs_total": step["pairs_total"],
            "expert_pairs_here": step["pairs_here"],
            "expert_tokens_max_sum": step["tokens_max"] / expert_layers,
            "expert_tokens_mean_sum":
                step["pairs_here"] / (expert_layers * held_experts),
            "experts_touched_sum": step["experts_touched"],
            "window_positions_held_sum":
                int(np.minimum(held, spec.window).sum()),
            # what one window layer's read streams for the live lanes:
            # each one's whole ring, whatever it holds (the read is over
            # every lane, so the dead lanes' rings are streamed besides)
            "ring_positions_capacity_sum":
                len(held) * spec.window if n_window else 0,
            "full_positions_held_sum": int(held.sum()),
            "slot_state_bytes_held_sum": len(held) * laid_out["lane_state"],
            "kv_bytes_held_sum": int(held.sum()) * laid_out["position"],
        }

    state = ("the state-space layers' state" if n_mamba
             else "the convolution layers' state" if n_conv
             else "the window layers' last positions")
    reasons = {
        "prefix_cache": f"a hit would need {state} at the prefix's end, and "
                        "a slot keeps them at its newest position only",
        "int8_pool": "quant/qtensor knows no per-slot leaf",
        "host_tier": "serve/kv_tier.py spills pages, and a sequence here "
                     "is its pages and its slot's state",
        "verify": f"a rejected tail would have to rewind {state}",
        "tensor_mesh": "no layout rule for folded KV heads, an expert axis "
                       "or a per-slot leaf",
    }

    return ServedModel(
        family="hybrid_moe",
        vocab_size=spec.vocab_size,
        num_layers=spec.num_layers,
        max_positions=None,
        init_cache=init_cache,
        prefill_chunk=prefill_chunk,
        decode=decode,
        scrub=scrub,
        poison=poison,
        programs={"decode": "_hybrid_decode_fn",
                  "prefill_chunk": "_hybrid_chunk_fn"},
        slot_state=True,
        last_logits_only=True,
        # a chunk reads every weight whatever its width: below 128 rows a
        # narrower program saves nothing and is one more to compile
        chunk_floor=128,
        count_step=count_step,
        refuses=frozenset(FEATURES),
        reasons=reasons,
    )
