"""The speculative decode step: draft K, verify K+1 in one jitted call.

``SpeculativeDecoder`` owns the compiled programs speculative serving
adds on top of an engine:

- the drafter's decode-shaped program (K sequential dispatches per spec
  step — device-to-device chained, no host sync between drafts);
- the **verify** program: ``forward_verify`` / ``forward_verify_paged``
  over all K+1 positions of every slot plus the acceptance rule IN-JIT —
  the longest draft prefix equal to the verifier's f32 argmax, the bonus
  token at the first mismatch, and the per-slot finiteness verdict the
  NaN quarantine reads — so one readback per spec step carries
  everything the scheduler needs (same one-designed-sync budget as
  ``engine.decode``);
- the batched **rollback** program: zero every cache position past each
  slot's kept prefix in ONE dispatch.  This is the jitted, batched form
  of ``engine.scrub_slot(slot, from_pos)`` — same position-granular
  semantics, pinned equivalent in ``tests/test_spec.py`` — because a
  per-slot host scrub every step would serialize the loop.  Rollback
  positions are strictly past each slot's committed history (decode
  region), so prefix-SHARED pages are never written: the paged program
  routes every zero through the slot's block table, and shared pages
  only ever cover prompt positions below ``pos``.

Greedy-only by construction: the acceptance rule compares argmaxes, so a
temperature > 0 engine is rejected at construction (the CLI rejects the
flag combination even earlier).  f32 KV cache only — the verify program
extends the decode==full-forward bit-exactness pin, which the int8
grid breaks (int8 *weights* are fine, and are exactly what the int8
drafter uses).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward_verify,
    forward_verify_paged,
)
from distributeddeeplearning_tpu.obs.attrib import tracked_jit
from distributeddeeplearning_tpu.obs.ledger import get_ledger
from distributeddeeplearning_tpu.obs.trace import get_tracer
from distributeddeeplearning_tpu.spec.drafter import Drafter, build_drafter


def _ledger_drafter_params(drafter):
    return getattr(drafter, "_dparams", None)


@dataclasses.dataclass
class SpecStepResult:
    """One spec step's readback: ``tokens[i, :accepted[i]+1]`` are slot
    ``i``'s committed tokens (accepted drafts + the verifier's bonus),
    ``finite`` is the quarantine verdict over exactly those positions."""

    tokens: np.ndarray  # [B, K1] the verifier's greedy token per position
    accepted: np.ndarray  # [B] accepted draft count, 0..draft_len
    finite: np.ndarray  # [B] bool
    draft_s: float  # host wall of the draft dispatch chain
    verify_s: float  # host wall of verify dispatch + readback


class SpeculativeDecoder:
    """Drive a drafter + batched verifier over a serving engine's cache.

    ``drafter`` is a kind string (``"truncated"`` / ``"int8"``) or any
    :class:`~..spec.drafter.Drafter` instance (tests inject adversarial
    ones).  ``draft_tokens`` is K — each spec step commits between 1 and
    K+1 tokens per slot.  The decoder mutates the engine's cache through
    the same donated-buffer discipline the engine's own programs use.
    """

    def __init__(
        self,
        engine,
        *,
        drafter: Union[str, Drafter] = "truncated",
        draft_tokens: int = 4,
        draft_layers: Optional[int] = None,
    ):
        if draft_tokens < 1:
            raise ValueError(
                f"draft_tokens must be >= 1, got {draft_tokens}"
            )
        model = getattr(engine, "model", None)
        if model is not None:
            # the verify programs are the OPT block's
            model.refuse("verify")
        if getattr(engine, "kv_dtype", "float32") != "float32":
            raise ValueError(
                "speculative decoding requires the f32 KV cache — the "
                "acceptance rule extends the decode==full-forward "
                "bit-exactness pin, which the int8 grid breaks (int8 "
                "WEIGHTS are supported: --draft-weights int8 drafts with "
                "them while the f32 model verifies)"
            )
        if getattr(engine, "temperature", 0.0) > 0.0:
            raise ValueError(
                "speculative decoding is greedy-only for now: the "
                "acceptance rule compares argmaxes, and sampled tokens "
                "would silently stop being equivalent to the non-"
                "speculative distribution"
            )
        if engine.mesh is not None and engine.mesh.devices.size > 1:
            raise ValueError(
                "speculative decoding is single-mesh for now (the "
                "verify/rollback programs carry no sharding annotations)"
            )
        self.engine = engine
        self.draft_tokens = draft_tokens
        if isinstance(drafter, Drafter):
            self.drafter = drafter
        else:
            if drafter == "truncated" and draft_layers is None:
                L = jax.tree_util.tree_leaves(
                    engine.params["blocks"]
                )[0].shape[0]
                draft_layers = max(1, L // 2)
            self.drafter = build_drafter(
                drafter, draft_layers=draft_layers
            )
        self.draft_layers = draft_layers
        self.drafter.bind(engine)
        self.drafter_name = self.drafter.name

        K1 = draft_tokens + 1
        num_heads = engine.num_heads
        paged = engine.kv_layout == "paged"
        self._paged = paged
        # verify rides the SAME attention kernel the engine decodes with
        # (ops.flash_decode): spec is f32-cache-only, where the flash
        # XLA twin is bitwise identical to the gather reference, so the
        # spec==sequential-decode pin is kernel-invariant off-TPU and
        # the TPU kernel streams the same pages decode does
        ver_kernel = getattr(engine, "decode_kernel", "gather")

        def _accept(logits, tokens, dlen):
            lg = logits.astype(jnp.float32)
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # [B, K1]
            # accepted = longest prefix where the verifier's argmax at
            # position j equals draft j+1 (columns past draft_len never
            # match — their proposals are padding)
            match = (greedy[:, :-1] == tokens[:, 1:]) & (
                jnp.arange(K1 - 1)[None] < dlen[:, None]
            )
            accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(
                axis=1
            )
            # quarantine verdict over exactly the emitted positions —
            # garbage lanes (j > draft_len) must not poison the slot
            emit = jnp.arange(K1)[None] <= accepted[:, None]
            finite = jnp.where(
                emit, jnp.isfinite(lg).all(axis=-1), True
            ).all(axis=1)
            return greedy, accepted, finite

        if paged:
            page_size = engine.page_size

            def _verify_fn(params, cache, tokens, pos, dlen, tables):
                logits, cache = forward_verify_paged(
                    params, tokens, cache, pos, dlen, tables,
                    num_heads=num_heads, page_size=page_size,
                    kernel=ver_kernel,
                )
                greedy, accepted, finite = _accept(logits, tokens, dlen)
                return greedy, accepted, finite, cache

            nb_static = engine.blocks_per_slot

            def _rollback_fn(cache, pos, keep, tables):
                # zero positions pos+m for m in [keep, K] — the rejected
                # draft tail (verify writes reach pos+K, the drafter's
                # clamped writes stay <= pos+draft_len <= pos+K).  Lanes
                # below keep, and lanes past the block table, route to
                # the scratch page — zeroing the dustbin is free.
                m = jnp.arange(1, K1)  # [K]
                wpos = pos[:, None] + m[None]  # [B, K]
                zero = m[None] >= keep[:, None]
                pidx = wpos // page_size
                inb = zero & (pidx < nb_static)
                rows = jnp.arange(pos.shape[0])[:, None]
                pages = jnp.where(
                    inb,
                    tables[rows, jnp.minimum(pidx, nb_static - 1)],
                    0,  # SCRATCH
                )
                offs = jnp.where(inb, wpos % page_size, 0)
                out = {}
                for key, leaf in cache.items():
                    out[key] = leaf.at[pages, :, offs].set(
                        jnp.zeros((), leaf.dtype)
                    )
                return out
        else:
            def _verify_fn(params, cache, tokens, pos, dlen):
                logits, cache = forward_verify(
                    params, tokens, cache, pos, dlen,
                    num_heads=num_heads, kernel=ver_kernel,
                )
                greedy, accepted, finite = _accept(logits, tokens, dlen)
                return greedy, accepted, finite, cache

            S = engine.max_seq

            def _rollback_fn(cache, pos, keep):
                m = jnp.arange(1, K1)
                wpos = pos[:, None] + m[None]
                zero = m[None] >= keep[:, None]
                tgt = jnp.where(zero, wpos, S)  # kept lanes -> OOB, dropped
                rows = jnp.arange(pos.shape[0])[:, None]
                out = {}
                for key, leaf in cache.items():
                    out[key] = leaf.at[rows, :, tgt].set(
                        jnp.zeros((), leaf.dtype), mode="drop"
                    )
                return out

        # attribution: verify/rollback cost rows per layout
        # (obs/attrib.py), and the drafter's own weight tree — sliced
        # truncated blocks, int8 drafter params — on the HBM ledger
        # under its semantic owner (leaves shared with the engine's
        # params are deduplicated by the ledger walk)
        tag = "spec.paged" if paged else "spec.dense"
        self._verify_jit = tracked_jit(f"{tag}.verify", jax.jit(
            _verify_fn, donate_argnums=(1,)
        ))
        self._rollback_jit = tracked_jit(f"{tag}.rollback", jax.jit(
            _rollback_fn, donate_argnums=(0,)
        ))
        get_ledger().register(
            "drafter_weights", self.drafter, _ledger_drafter_params
        )

    # -- the draft -> verify hot loop ---------------------------------------
    def step(
        self, tokens: np.ndarray, pos: np.ndarray, draft_len: np.ndarray
    ) -> SpecStepResult:
        """One speculative step for every slot: draft K tokens (device-
        chained dispatches), verify all K+1 positions in one call, read
        back the acceptance.  ``draft_len[i]`` caps slot ``i``'s real
        drafts (0 = that slot runs a plain decode step through the
        verify program); the caller guarantees
        ``pos[i] + draft_len[i] < max_seq``."""
        engine = self.engine
        trace = get_tracer()
        t_dev = jnp.asarray(tokens, jnp.int32)
        pos_dev = jnp.asarray(pos, jnp.int32)
        dlen_dev = jnp.asarray(draft_len, jnp.int32)
        t0 = time.perf_counter()
        cols = [t_dev]
        cur = t_dev
        with trace.span("serve/spec.draft_dispatch", k=self.draft_tokens):
            for j in range(self.draft_tokens):
                # clamp each slot's draft position at pos+draft_len:
                # lanes past their cap re-write that (rolled-back or
                # verify-overwritten) position instead of walking into
                # pages/positions the slot never reserved
                pos_j = pos_dev + jnp.minimum(jnp.int32(j), dlen_dev)
                cur, cache = self.drafter.propose(
                    engine._cache, cur, pos_j
                )
                engine._cache = cache
                cols.append(cur)
        t1 = time.perf_counter()
        tokens_mat = jnp.stack(cols, axis=1)  # [B, K1]
        with trace.span("serve/spec.verify_dispatch"):
            if self._paged:
                greedy, accepted, finite, cache = self._verify_jit(
                    engine.params, engine._cache, tokens_mat, pos_dev,
                    dlen_dev, jnp.asarray(engine.block_tables),
                )
            else:
                greedy, accepted, finite, cache = self._verify_jit(
                    engine.params, engine._cache, tokens_mat, pos_dev,
                    dlen_dev,
                )
            engine._cache = cache
        # THE one designed sync of the spec step (the scheduler needs the
        # committed ids to stream/complete) — everything above is
        # dispatch-only, same budget as engine.decode's token readback.
        # The three marked lines below ARE the spec region's sync_budget
        # in analysis/regions.py: adding a sync here fails `ddlt lint`.
        out = np.asarray(greedy)  # sync-ok: the designed token readback
        acc = np.asarray(accepted)  # sync-ok: rides the same readback
        fin = np.asarray(finite)  # sync-ok: rides the same readback
        t2 = time.perf_counter()
        engine.last_finite = fin
        return SpecStepResult(
            tokens=out, accepted=acc, finite=fin,
            draft_s=t1 - t0, verify_s=t2 - t1,
        )

    def rollback(self, pos: np.ndarray, keep: np.ndarray) -> None:
        """Zero every slot's cache positions ``>= pos + keep`` up through
        the spec step's write horizon (``pos + K``) in one dispatch —
        the batched ``scrub_slot(slot, from_pos=pos+keep)``.  ``keep ==
        draft_tokens + 1`` skips a slot entirely (full acceptance: there
        is no rejected tail to scrub)."""
        engine = self.engine
        pos_dev = jnp.asarray(pos, jnp.int32)
        keep_dev = jnp.asarray(keep, jnp.int32)
        if self._paged:
            engine._cache = self._rollback_jit(
                engine._cache, pos_dev, keep_dev,
                jnp.asarray(engine.block_tables),
            )
        else:
            engine._cache = self._rollback_jit(
                engine._cache, pos_dev, keep_dev
            )
