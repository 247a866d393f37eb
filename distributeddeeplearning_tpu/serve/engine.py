"""Jitted prefill/decode engine over the stacked-transformer LM.

The prefill/decode split that TPU serving economics hinge on (arxiv
2605.25645): prompts run ONCE through the full parallel forward — the
Pallas flash-attention kernel path, compute-bound, O(P²) FLOPs but O(P)
memory — and every generated token runs a single-token decode step that is
pure cache traffic: O(S·d) per layer, bandwidth-bound, no S² anywhere.

Three compiled programs:

- ``prefill``: ``forward_prefill`` on a [1, P] padded prompt bucket
  (power-of-two buckets bound recompiles), returning the last real
  position's logits plus the per-layer K/V;
- ``insert``: one ``dynamic_update_slice`` of those K/V into a cache slot
  (slot index traced — one executable serves every slot), cache donated;
- ``decode``: ``forward_decode`` over ALL slots at their own positions +
  sampling, cache donated so the [slots, L, S, h, hd] buffers update in
  place.

Sampling follows ``train/step.py``'s RNG convention: one base key, the
step counter folded in per call (``jax.random.fold_in``), so a serve run
is exactly reproducible from (seed, request order) alone.

With a ``mesh``, every device placement resolves through the partition-
rule layout table (``parallel.sharding.LAYOUT_RULES``): the cache shards
slots over the data axes and heads over ``tensor``
(``kv_cache.cache_sharding``), and params shard Megatron-style over the
``tensor`` axis — column-parallel qkv/w_in, row-parallel proj/w_out,
vocab-parallel embed/head — so a ``data=1 × tensor=N`` mesh serves a
model N× wider than one chip's HBM (``tensor_parallel_engine``).  A pure-
data mesh degenerates to the old layout (every ``tensor`` rule maps onto
an axis of size 1, i.e. replication); no spec is hand-wired here.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.obs.attrib import tracked_jit
from distributeddeeplearning_tpu.obs.ledger import get_ledger
from distributeddeeplearning_tpu.obs.trace import get_tracer
from distributeddeeplearning_tpu.models.pipelined_transformer import (
    forward_decode,
    forward_prefill,
)
from distributeddeeplearning_tpu.ops.flash_attention import auto_block_tiles
from distributeddeeplearning_tpu.ops.flash_decode import (
    dense_block,
    flash_impl,
    resolve_kernel,
)
from distributeddeeplearning_tpu.parallel import sharding as layout
from distributeddeeplearning_tpu.parallel.mesh import data_parallel_size
from distributeddeeplearning_tpu.quant.calibrate import (
    bf16_matmul_params,
    params_dtype,
)
from distributeddeeplearning_tpu.quant.qtensor import QTensor
from distributeddeeplearning_tpu.serve.kv_cache import (
    OutOfPages,
    PageAllocator,
    SCRATCH_PAGE,
    cache_bytes,
    cache_sharding,
    init_cache,
    insert_sequence,
    page_bytes,
    pages_for,
    slot_state_bytes,
)
from distributeddeeplearning_tpu.serve.kv_tier import HostPageTier
from distributeddeeplearning_tpu.serve.served_model import (
    ServedModel,
    opt_model,
)

logger = logging.getLogger("ddlt.serve.engine")

NEG_BIG = -1e30


# -- HBM-ledger providers (module-level: the ledger holds the ENGINE via
# weakref and calls these with it, so no closure can pin a dead engine's
# cache alive through its own accounting) ----------------------------------

def _ledger_params(engine):
    return engine.params


def _ledger_kv_values(engine):
    return {
        k: v for k, v in engine._cache.items() if not k.endswith("_scale")
    }


def _ledger_kv_scales(engine):
    return {
        k: v for k, v in engine._cache.items() if k.endswith("_scale")
    }


def _leaf_subset_page_bytes(cache, *, scales: bool) -> int:
    """Per-page bytes of just the value (or just the scale) leaves —
    the committed-bytes granule for the paged pool's ledger owners."""
    return page_bytes({
        key: leaf for key, leaf in cache.items()
        if key.endswith("_scale") == scales
    })


def _ledger_host_tier_bytes(engine):
    tier = getattr(engine, "tier", None)
    return 0 if tier is None else tier.used_bytes()


def _register_engine_owners(engine, ledger=None) -> None:
    """Put the engine's device state on the HBM ledger (default: the
    process ledger) by semantic owner: weights under ``params``, K/V
    pools under ``kv_pages``, the int8 layout's f32 scales under
    ``kv_scales`` — the decomposition the attribution artifact and the
    crash dumps report.  Paged engines also report COMMITTED bytes
    (pages actually in use × per-page bytes) so the admission forecast
    prices demand, not the preallocated reservation.  An attached host
    tier registers its pool under ``kv_host_pages`` as a HOST owner:
    attributed in snapshots and fleet watermarks, excluded from the HBM
    forecast (host RAM is not device memory)."""
    if ledger is None:
        ledger = get_ledger()
    ledger.register("params", engine, _ledger_params)
    paged = getattr(engine, "kv_layout", "dense") == "paged"
    if paged:
        val_pb = _leaf_subset_page_bytes(engine._cache, scales=False)
        # per-slot state (a two-kind cache's window rings) is committed
        # whole from the start; pages as they are handed out
        held = slot_state_bytes(engine._cache)
        ledger.register(
            "kv_pages", engine, _ledger_kv_values,
            committed=lambda e, pb=val_pb, held=held: (
                e.allocator.pages_in_use * pb + held
            ),
        )
    else:
        ledger.register("kv_pages", engine, _ledger_kv_values)
    if "k_scale" in engine._cache:
        if paged:
            sc_pb = _leaf_subset_page_bytes(engine._cache, scales=True)
            ledger.register(
                "kv_scales", engine, _ledger_kv_scales,
                committed=lambda e, pb=sc_pb: (
                    e.allocator.pages_in_use * pb
                ),
            )
        else:
            ledger.register("kv_scales", engine, _ledger_kv_scales)
    if getattr(engine, "tier", None) is not None:
        ledger.register_host(
            "kv_host_pages", engine, _ledger_host_tier_bytes
        )


def sample_logits(
    logits: jax.Array,
    rng: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
) -> jax.Array:
    """Greedy / temperature / top-k sampling over [..., vocab] logits.

    ``temperature <= 0`` is greedy argmax (rng unused — a greedy run is
    bitwise deterministic); otherwise logits outside the top ``top_k``
    (when set) are masked before a temperature-scaled categorical draw.
    The mask keeps EXACTLY ``top_k`` logits: ties at the k-th value are
    broken deterministically by ``lax.top_k``'s lowest-index-first order
    (a ``logits < kth`` threshold mask would let every tied logit through
    and sample from more than ``top_k`` candidates).
    """
    if top_k is not None and top_k < 1:
        # top_k=0 would otherwise surface as an opaque broadcast error
        # deep inside the jitted prefill
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k is not None and top_k < logits.shape[-1]:
        idx = jax.lax.top_k(logits, top_k)[1]  # [..., k], ties by index
        keep = jax.nn.one_hot(
            idx, logits.shape[-1], dtype=jnp.bool_
        ).any(axis=-2)
        logits = jnp.where(keep, logits, NEG_BIG)
    return jax.random.categorical(rng, logits / temperature, axis=-1).astype(
        jnp.int32
    )


def prompt_bucket(n: int, max_seq: int, floor: int = 8) -> int:
    """Smallest power-of-two >= n (>= floor), capped at max_seq — the
    prefill compile bucket for a prompt of ``n`` tokens.  Public so
    drivers (``bench.py --serve`` warmup) can enumerate the buckets a
    request set will compile."""
    b = floor
    while b < n:
        b *= 2
    return min(b, max_seq)


def _f32_product_is_one_bf16_pass() -> bool:
    """Whether a default-precision float32 matmul on this backend rounds
    both operands to bfloat16 and accumulates in float32 (the TPU's one
    MXU pass): where it does, weights rounded once serve the very product
    the float32 ones would, and nowhere else."""
    return (
        jax.default_backend() == "tpu"
        and jax.config.jax_default_matmul_precision in (None, "default")
    )


def _matmul_operands(params):
    """The tree an engine holds and its programs read, for the tree its
    caller hands in (at construction and at every reload): where
    :func:`_f32_product_is_one_bf16_pass`, float32 matmul weights become
    the bfloat16 operands the device's matmul reads
    (``quant.calibrate.bf16_matmul_params``: one copy, made here, not one
    a call inside every program); anywhere else, and for int8 or bf16
    weights, the caller's own tree."""
    if not _f32_product_is_one_bf16_pass():
        return params
    t0 = time.perf_counter()
    held = jax.block_until_ready(bf16_matmul_params(params))
    if held is not params:
        logger.info(
            "engine: float32 matmul weights rounded to bfloat16 once "
            "(%.3f GB handed in, %.3f GB held, %.3f s)",
            cache_bytes(params) / 1e9, cache_bytes(held) / 1e9,
            time.perf_counter() - t0,
        )
    return held


def _matmul_dtype(params) -> str:
    """The dtype of the weights the matmuls read (``head`` stands for all
    of them: both weight transforms treat the matmul leaves alike; a
    model whose head is its embedding has no ``head`` leaf)."""
    head = params["head"] if "head" in params else params["embed"]
    return "int8" if isinstance(head, QTensor) else str(head.dtype)


def _check_reload_tree(old, new) -> None:
    """Reload admissibility: the new weight set must be drop-in for the
    compiled programs — same pytree structure, and every leaf aval
    (shape, dtype) identical.  Anything else would silently recompile
    every decode program mid-serve (or worse, reshape K/V math); refuse
    loudly instead."""
    if jax.tree_util.tree_structure(new) != jax.tree_util.tree_structure(old):
        raise ValueError(
            "reload_params: new params tree structure differs from the "
            "engine's (different model family / quantization state?) — "
            "a live reload must be weight-value-only"
        )
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(old)[0],
        jax.tree_util.tree_flatten_with_path(new)[0],
    ):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(
                "reload_params: leaf "
                f"{jax.tree_util.keystr(path)} changed aval "
                f"({a.shape}/{a.dtype} -> {b.shape}/{b.dtype}) — same-"
                "shape weight sets only (compiled programs stay live)"
            )


def _validate_model_dims(params, *, num_heads: int, max_seq: int, top_k):
    """Construction-time checks both engine layouts share; returns
    ``(d_model, num_layers, head_dim)`` from the param shapes."""
    pos_table = params["pos"].shape[0]
    if max_seq > pos_table:
        raise ValueError(
            f"max_seq {max_seq} exceeds the model's position table "
            f"{pos_table} — re-init the params with max_len >= max_seq"
        )
    d_model = params["embed"].shape[1]
    if d_model % num_heads:
        raise ValueError(
            f"d_model {d_model} not divisible by heads {num_heads}"
        )
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    return d_model, params["blocks"]["qkv"].shape[0], d_model // num_heads


def data_parallel_engine(params, *, num_heads: int, batch_slots: int,
                         max_seq: int, **engine_kw):
    """Engine over all visible devices when the slot count allows it.

    The ONE mesh-gating rule both serving entry points (``ddlt serve``,
    ``bench.py --serve``) share: a pure-DP mesh when ``batch_slots``
    divides over the device count (``MeshSpec()``'s data axis absorbs
    everything, so data×fsdp == device count), single-device otherwise.
    Returns ``(engine, mesh)`` — ``mesh`` is None in the single case.
    """
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1 and batch_slots % n_dev == 0:
        from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec())
        logger.info("serve: cache slots sharded over %d devices", n_dev)
    engine = InferenceEngine(
        params, num_heads=num_heads, batch_slots=batch_slots,
        max_seq=max_seq, mesh=mesh, **engine_kw,
    )
    return engine, mesh


def tensor_parallel_engine(params, *, tp: int, num_heads: int,
                           batch_slots: int, max_seq: int,
                           kv_layout: str = "dense", **engine_kw):
    """Engine with weights tensor-parallel over the first ``tp`` devices.

    Builds a ``data=1 × tensor=tp`` mesh and hands it to the requested
    engine layout; every placement resolves through the partition-rule
    table, so qkv/w_in shard column-parallel, proj/w_out row-parallel,
    embed/head vocab-parallel, and the KV cache's head dim splits too —
    per-chip param HBM ≈ 1/tp.  ``tp=1`` returns the plain single-device
    engine (the bench baseline).  Returns ``(engine, mesh)``; ``mesh`` is
    None for ``tp=1``.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    mesh = None
    if tp > 1:
        from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh

        devs = jax.devices()
        if tp > len(devs):
            raise ValueError(
                f"tp={tp} exceeds the {len(devs)} visible devices"
            )
        mesh = create_mesh(
            MeshSpec(data=1, tensor=tp), devices=devs[:tp]
        )
    cls = (
        PagedInferenceEngine if kv_layout == "paged" else InferenceEngine
    )
    engine = cls(
        params, num_heads=num_heads, batch_slots=batch_slots,
        max_seq=max_seq, mesh=mesh, **engine_kw,
    )
    return engine, mesh


class InferenceEngine:
    """KV-cached generation over a ``pipelined_transformer`` param pytree.

    The engine owns the device state (params + cache) and exposes exactly
    the two verbs the continuous-batching scheduler needs:

    - ``prefill(slot, prompt) -> first sampled token`` — run the prompt,
      seed the slot's cache lines;
    - ``decode(tokens, pos) -> next tokens`` — one step for ALL slots
      (the scheduler masks the inactive ones).

    This is the DENSE layout (``kv_layout="dense"``): every slot reserves
    ``max_seq`` cache positions.  :class:`PagedInferenceEngine` is the
    pay-per-token alternative; both satisfy the same scheduler protocol
    (``can_admit`` / ``release`` / ``prefill_compiles``).

    ``prefill_attention="flash"`` (default) runs the prompt pass through
    the Pallas kernel, one compiled program per prompt bucket; the top
    bucket is ``max_seq`` itself, so ``max_seq`` must be a length the
    kernel's auto-selected blocks tile (up to 1024, or a multiple of
    128) — refused at construction otherwise, never rerouted to dense.

    Which weights it holds (the caller's own, or on a TPU a copy of the
    float32 ones with the matmul leaves rounded to bfloat16 once) is
    :class:`PagedInferenceEngine`'s rule: see there.
    """

    def __init__(
        self,
        params,
        *,
        num_heads: int,
        batch_slots: int,
        max_seq: int,
        mesh=None,
        prefill_attention: str = "flash",
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        cache_dtype=None,
        rng: Optional[jax.Array] = None,
        pad_id: int = 0,
        decode_kernel: str = "auto",
    ):
        self.kv_layout = "dense"
        self.chunked_prefill = False
        self.prefill_attention = prefill_attention
        # "flash" = ops.flash_decode (Pallas kernel on TPU; fused-XLA
        # twin elsewhere, where it is bitwise == gather for f32 caches);
        # "gather" = the legacy dense cache read.  Resolved once so the
        # compiled programs and the provenance the reports carry agree;
        # decode_impl is what actually runs HERE (pallas | xla | gather).
        self.decode_kernel = resolve_kernel(decode_kernel)
        self.decode_impl = flash_impl(self.decode_kernel)
        if self.decode_impl == "pallas":
            dense_block(max_seq)  # raises on a length the kernel can't tile
        if prefill_attention == "flash" and not auto_block_tiles(max_seq):
            raise ValueError(
                f"max_seq {max_seq} is not a length the flash prefill "
                "kernel tiles (the top prompt bucket is max_seq itself): "
                "use max_seq <= 1024 or a multiple of 128, or "
                "prefill_attention='dense'"
            )
        # distinct compiled prefill shapes (each new power-of-two bucket
        # is a mid-run jit recompile — ServeReport surfaces the count so
        # benchmark warmup can prove it drove them all to 0)
        self.prefill_compiles = 0
        self._seen_buckets: set = set()
        _, num_layers, head_dim = _validate_model_dims(
            params, num_heads=num_heads, max_seq=max_seq, top_k=top_k
        )
        # provenance the ServeReport carries: an int8 artifact must be
        # distinguishable from an f32 one without diffing configs.
        # weights_dtype is what was handed in; matmul_dtype and
        # weights_bytes are what the engine holds and its matmuls read
        self.weights_dtype = params_dtype(params)
        params = _matmul_operands(params)
        self.matmul_dtype = _matmul_dtype(params)
        self.weights_bytes = cache_bytes(params)  # any pytree's leaves
        self.params = params
        self.num_heads = num_heads
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.mesh = mesh
        self.pad_id = pad_id
        self.vocab_size = params["head"].shape[1]
        # exposed for the spec decoder's greedy-only guard
        self.temperature = float(temperature)
        if cache_dtype is None:
            cache_dtype = params["embed"].dtype
        self.kv_dtype = np.dtype(cache_dtype).name
        self._base_rng = jax.random.key(0) if rng is None else rng
        self._sample_step = 0
        # per-slot logit-finiteness verdict of the LAST decode step,
        # computed in-jit alongside sampling (the scheduler's NaN
        # quarantine reads it from the same readback — no extra sync)
        self.last_finite: Optional[np.ndarray] = None

        self._cache = init_cache(
            batch_slots=batch_slots,
            num_layers=num_layers,
            max_seq=max_seq,
            num_heads=num_heads,
            head_dim=head_dim,
            dtype=cache_dtype,
        )

        sharded = mesh is not None and mesh.devices.size > 1
        self.tp = layout.tensor_parallel_size(mesh) if sharded else 1
        self.layout_rules = layout.layout_rules_provenance()
        self._params_sharding = None  # reload re-places onto the same layout
        if sharded:
            if batch_slots % data_parallel_size(mesh):
                raise ValueError(
                    f"batch_slots {batch_slots} not divisible by the mesh's "
                    f"data axes {dict(mesh.shape)}"
                )
            if num_heads % self.tp:
                raise ValueError(
                    f"num_heads {num_heads} not divisible by the mesh's "
                    f"tensor axis ({self.tp}) — TP shards attention heads"
                )
            # every placement below comes out of the partition-rule layout
            # table; nothing here names a mesh axis directly
            c_shard = cache_sharding(mesh, quantized=self.kv_dtype == "int8")
            rep = layout.replicated(mesh)
            slot_vec = layout.io_sharding(mesh, "tokens", shape=(batch_slots,))
            scalar = layout.io_sharding(mesh, "step", shape=())
            p_shard = layout.resolve_shardings(mesh, params, prefix="params")
            self._params_sharding = p_shard
            self.params = jax.device_put(params, p_shard)
            self._cache = jax.device_put(self._cache, c_shard)
            # prefill's emitted K/V carry the cache head sharding (same
            # kv_dense rules — [1, L, P, h, hd] rides the 5-dim entry
            # list), so insert never pays a resharding copy.  Resolved
            # WITH the shape: the one-sequence leading dim cannot split
            # over the data axes, and the table's divisibility drop
            # replicates it (the bucket dim P is never sharded)
            seed = jax.ShapeDtypeStruct(
                (1, num_layers, max_seq, num_heads, head_dim),
                self._cache["k"].dtype,
            )
            kv_seed = layout.resolve_shardings(
                mesh, {"k": seed, "v": seed}, prefix="kv_dense"
            )
            decode_in = (p_shard, c_shard, slot_vec, slot_vec, scalar)
            decode_out = (rep, rep, c_shard)  # tokens, finite, cache
            insert_in = (c_shard, kv_seed["k"], kv_seed["v"], scalar)
            jit_kw = dict(in_shardings=decode_in, out_shardings=decode_out)
            insert_kw = dict(in_shardings=insert_in, out_shardings=c_shard)
            prefill_kw = dict(
                out_shardings=(rep, kv_seed["k"], kv_seed["v"])
            )
        else:
            jit_kw = {}
            insert_kw = {}
            prefill_kw = {}

        temperature = float(temperature)
        base_rng = self._base_rng

        def _sample(logits, step):
            return sample_logits(
                logits,
                jax.random.fold_in(base_rng, step),
                temperature=temperature,
                top_k=top_k,
            )

        prefill_attention_fn = None
        if sharded and prefill_attention == "flash":
            # a bare pallas_call cannot be partitioned (the TPU compiler
            # refuses it under a multi-device jit): the kernel runs per
            # shard — local heads under TP, the one prompt whole on every
            # chip of a data mesh
            from distributeddeeplearning_tpu.ops.flash_attention import (
                make_flash_attention,
            )

            prefill_attention_fn = make_flash_attention(
                mesh=mesh, causal=True
            )

        def _prefill_fn(params, tokens, length):
            logits, k, v = forward_prefill(
                params, tokens, num_heads=num_heads,
                attention=prefill_attention,
                attention_fn=prefill_attention_fn,
            )
            last = jax.lax.dynamic_index_in_dim(
                logits, length - 1, axis=1, keepdims=False
            )  # [1, vocab] — the last REAL position, not the padding
            return last, k, v

        def _insert_fn(cache, k, v, slot):
            return insert_sequence(cache, k, v, slot)

        dec_kernel = self.decode_kernel

        def _decode_fn(params, cache, tokens, pos, step):
            logits, cache = forward_decode(
                params, tokens, cache, pos, num_heads=num_heads,
                kernel=dec_kernel, mesh=mesh,
            )
            # per-slot health verdict rides the step (one [slots] bool —
            # the NaN-quarantine signal, free next to the token readback)
            finite = jnp.isfinite(logits).all(axis=-1)
            return _sample(logits, step), finite, cache

        def _scrub_fn(cache, slot, from_pos):
            # zero positions >= from_pos of one slot's row, all leaves;
            # slot AND from_pos are traced so quarantine/rollback never
            # pay a recompile per call site
            keep_mask = jnp.arange(max_seq) < from_pos  # [S]
            out = {}
            for key, leaf in cache.items():
                row = leaf[slot]  # [L, S, ...]
                m = keep_mask.reshape((1, max_seq) + (1,) * (row.ndim - 2))
                out[key] = leaf.at[slot].set(
                    jnp.where(m, row, jnp.zeros((), leaf.dtype))
                )
            return out

        # one compiled prefill per prompt bucket (jit cache keyed on P);
        # every program is tracked in the attribution registry (cost
        # recorded at first compile — obs/attrib.py) under a name that
        # carries layout + cache dtype, so f32 and int8 engines report
        # distinguishable cost rows
        tag = f"serve.dense.{self.kv_dtype}"
        self._prefill_jit = tracked_jit(
            f"{tag}.prefill", jax.jit(_prefill_fn, **prefill_kw)
        )
        self._insert_jit = tracked_jit(f"{tag}.insert", jax.jit(
            _insert_fn, donate_argnums=(0,), **insert_kw
        ))
        self._decode_jit = tracked_jit(f"{tag}.decode", jax.jit(
            _decode_fn, donate_argnums=(1,), **jit_kw
        ))
        self._sample_jit = jax.jit(_sample)
        self._scrub_jit = tracked_jit(f"{tag}.scrub", jax.jit(
            _scrub_fn, donate_argnums=(0,)
        ))
        _register_engine_owners(self)
        logger.info(
            "engine: %d slots x seq %d, %d layers, cache %.1f MB (%s)%s",
            batch_slots, max_seq, num_layers,
            cache_bytes(self._cache) / 1e6, np.dtype(cache_dtype).name,
            " sharded" if sharded else "",
        )

    @property
    def cache(self):
        return self._cache

    def kernel_programs(self):
        """The jitted programs that asked for a Pallas kernel (flash
        prefill, flash decode) — what ``obs.attrib.mosaic_call_counts``
        inspects to prove the kernel is in them."""
        progs = []
        if self.prefill_attention == "flash":
            progs.append(self._prefill_jit)
        if self.decode_kernel != "gather":
            progs.append(self._decode_jit)
        return progs

    def kv_bytes(self) -> int:
        """Total KV pool bytes (the HBM the layout RESERVES)."""
        return cache_bytes(self._cache)

    def kv_bytes_peak(self) -> int:
        """Peak KV bytes actually committed to sequences — for the dense
        layout that is the whole reservation (every slot holds ``max_seq``
        positions whether used or not), which is exactly the number the
        paged layout exists to shrink."""
        return cache_bytes(self._cache)

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Dense slots always fit a (validated) request — admission is
        gated by the scheduler's free-slot list alone."""
        return True

    def admit_bytes(self, prompt_len: int, max_new_tokens: int) -> int:
        """Incremental committed HBM a request would add — zero for the
        dense layout (every slot's reservation is committed up front),
        so the scheduler's ledger forecast admits on headroom alone."""
        return 0

    def release(self, slot: int) -> None:
        """No device state to reclaim: the slot's stale K/V stay masked
        behind the next occupant's positions."""

    def _next_step(self) -> int:
        step = self._sample_step
        self._sample_step += 1
        return step

    def prefill(self, slot: int, prompt: Sequence[int]) -> int:
        """Run ``prompt`` through the model, seed ``slot``'s cache lines,
        and return the first sampled continuation token (its K/V enter the
        cache on the first decode step, at position ``len(prompt)``)."""
        length = len(prompt)
        if not length:
            raise ValueError("empty prompt")
        if length >= self.max_seq:
            raise ValueError(
                f"prompt length {length} leaves no room to generate "
                f"(max_seq {self.max_seq})"
            )
        if not 0 <= slot < self.batch_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.batch_slots})")
        bucket = prompt_bucket(length, self.max_seq)
        if bucket not in self._seen_buckets:
            self._seen_buckets.add(bucket)
            self.prefill_compiles += 1
        tokens = np.full((1, bucket), self.pad_id, np.int32)
        tokens[0, :length] = np.asarray(prompt, np.int32)
        with get_tracer().span(
            "serve/engine.prefill_dispatch", bucket=bucket
        ):
            last, k, v = self._prefill_jit(
                self.params, jnp.asarray(tokens), jnp.int32(length)
            )
            self._cache = self._insert_jit(
                self._cache, k, v, jnp.int32(slot)
            )
        tok = self._sample_jit(last, jnp.int32(self._next_step()))
        # the scheduler's ``serve/prefill`` around this names the request;
        # duck-typed engines share this signature, so no uid comes in
        with get_tracer().span("serve/engine.first_token_fetch", slot=slot):
            return int(np.asarray(tok)[0])

    def decode(self, tokens: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """One decode step for every slot: ``tokens[i]`` at ``pos[i]`` →
        the sampled next token per slot.  Inactive slots still compute
        (fixed batch shape is what makes the step a single executable);
        the scheduler ignores their outputs and their cache writes stay
        masked behind the slot's position."""
        # three spans, one step: the argument upload (each jnp.asarray is
        # a tiny device program of its own), the jitted call, and the
        # readback — the scheduler's one designed sync (it needs the token
        # ids), where the host waits out the device's step
        trace = get_tracer()
        step = self._next_step()
        with trace.span("serve/engine.decode_upload", step=step):
            args = (
                self.params,
                self._cache,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32),
                jnp.int32(step),
            )
        with trace.span("serve/engine.decode_dispatch", step=step):
            toks, finite, self._cache = self._decode_jit(*args)
        with trace.span("serve/engine.decode_fetch", step=step):
            # the finite readback piggybacks on the token sync (same
            # computation, already materialized)
            self.last_finite = np.asarray(finite)
            return np.asarray(toks)

    # -- fault injection / quarantine hooks --------------------------------
    def poison_slot(self, slot: int, pos: int) -> None:
        """Corrupt ``slot``'s K history at ``pos`` with NaN (the
        ``decode_nan`` fault's entry point — deterministic chaos only).

        K ONLY, never V: a NaN key makes the poisoned slot's own scores
        NaN (the quarantine signal) while positions masked for a FUTURE
        occupant are replaced by the -1e30 fill *before* softmax, so the
        NaN never escapes the victim.  A NaN *value* would leak through
        masking — softmax gives masked lanes exactly-0.0 weights and
        ``0.0 * NaN == NaN``."""
        c = dict(self._cache)
        if "k_scale" in c:  # int8 K can't hold NaN — poison the f32 scales
            c["k_scale"] = c["k_scale"].at[slot, :, pos].set(jnp.nan)
        else:
            c["k"] = c["k"].at[slot, :, pos].set(jnp.nan)
        self._cache = c

    def scrub_slot(self, slot: int, from_pos: int = 0) -> None:
        """Zero the slot's cache row from position ``from_pos`` on.

        Positions ``< from_pos`` are preserved BIT-EXACT — the partial
        form is the rollback primitive speculative decoding's rejected
        tails go through (``from_pos`` = first rejected position) and
        what the NaN quarantine calls with ``from_pos`` = the delivery's
        prompt length (scrub exactly the decode-written region).  Dense
        rows are fully private, so there is no shared state to protect.
        One compiled program serves every (slot, from_pos): both are
        traced."""
        self._cache = self._scrub_jit(
            self._cache, jnp.int32(slot), jnp.int32(from_pos)
        )

    # -- live weight reload ------------------------------------------------
    def reload_params(self, params) -> None:
        """Swap the engine's weight set IN PLACE — the live-reload verb.

        Same tree / shapes / dtypes only (:func:`_check_reload_tree`), so
        every compiled program (params travel as jit ARGUMENTS, keyed on
        avals) and the KV cache buffers stay untouched — the swap is one
        ``device_put`` onto the engine's existing param layout.  The
        caller hands in the kind of tree it built the engine from: it
        goes through :func:`_matmul_operands` first, as at construction,
        and what that gives is held against what the engine holds.  The
        scheduler applies reloads only at an idle barrier between decode
        steps (``request_reload``), so no request ever sees two weight
        sets."""
        params = _matmul_operands(params)
        _check_reload_tree(self.params, params)
        if self._params_sharding is not None:
            params = jax.device_put(params, self._params_sharding)
        self.params = params
        logger.info("engine: params reloaded in place (dense layout)")


class PrefillTask:
    """In-flight chunked prefill of one request: the scheduler advances it
    one chunk at a time (``PagedInferenceEngine.prefill_step``) between
    decode steps, so a long prompt never stalls running requests for its
    full O(P²) pass."""

    __slots__ = ("slot", "prompt", "pages", "offset", "shared_tokens", "uid")

    def __init__(self, slot, prompt, pages, offset, shared_tokens, uid=None):
        self.slot = slot
        self.prompt = list(prompt)
        self.pages = pages  # this sequence's block table (physical ids)
        self.offset = offset  # tokens already in cache (shared + chunked)
        self.shared_tokens = shared_tokens  # prefix-cache hit length
        self.uid = uid  # the request's, for the spans

    @property
    def done(self) -> bool:
        return self.offset >= len(self.prompt)


class DispatchedStep(NamedTuple):
    """A decode step that was launched and not read yet
    (``PagedInferenceEngine.decode_dispatch`` -> ``decode_fetch``): the
    engine's number for it (``step`` on its spans), its outputs as they lie
    on the device (``logits`` and ``counts`` None where the step returns
    none), and the live lanes' positions for what the model's step
    counted."""

    step: int
    tokens: Any
    finite: Any
    logits: Any
    counts: Any
    live_pos: Optional[np.ndarray]


class PagedInferenceEngine:
    """Paged-KV-cache generation: HBM by actual tokens, not ``max_seq``.

    Same scheduler verbs as :class:`InferenceEngine` plus the paged
    extras:

    - ``can_admit(prompt_len, budget)`` — enough pages free (admission is
      bounded by the POOL, not a fixed per-slot reservation)?
    - ``prefill_begin(slot, prompt, budget) -> PrefillTask`` — allocate
      the sequence's pages (reusing prefix-cache hits: leading full pages
      whose token ids match skip prefill entirely) and map its block
      table;
    - ``prefill_step(task) -> first token | None`` — run ONE prompt chunk
      through the compiled chunk program (``forward_prefill_chunk``);
      returns the first sampled token once the last chunk lands;
    - ``decode(tokens, pos)`` — one step for all slots via block-table
      gather (``forward_decode_paged``); it is ``decode_dispatch`` (upload
      and launch, nothing read) and ``decode_fetch`` (the step's one read)
      back to back, and a loop that calls the halves itself can launch a
      step before it reads the one before: the sampled tokens stay on the
      device and feed the next step from there;
    - ``release(slot)`` — decref the slot's pages; full prompt pages
      stay in the prefix table (reclaimable) for future hits.

    Decode math is bit-identical to the dense engine (the gathered page
    view IS the dense key sequence), so greedy runs produce the same
    tokens under either layout — ``tests/test_paged_cache.py`` pins it.
    A ``mesh`` must be tensor-only (``data×fsdp == 1``): the page-pool
    axis never shards (the block-table gather must stay chip-local), so
    TP splits weights and the cache's HEAD dim through the partition-rule
    layout table while page addressing stays on-chip.

    The weights the engine holds are the ones its matmuls read.  Where a
    default-precision float32 product is one bf16 pass (a TPU:
    :func:`_f32_product_is_one_bf16_pass`) float32 parameters are served
    from a copy whose matmul leaves were rounded to bfloat16 once, at
    construction and again at every ``reload_params`` — the product the
    device computed anyway, without rounding every weight stack on every
    call — and the engine keeps no reference to the float32 stacks (the
    caller's tree is untouched; ``embed``, ``pos`` and the LayerNorm
    scales are shared with it).  Anywhere else, and for int8 or bf16
    weights, ``engine.params`` is the very tree that was handed in.
    ``weights_dtype`` names what was handed in; ``matmul_dtype`` and
    ``weights_bytes`` what is held (the ``ServeReport`` carries all three).

    The model comes through one description, ``model`` (a
    :class:`~.served_model.ServedModel`: its two forwards, its cache
    layout, its programs' names, what it refuses); ``num_heads`` alone
    binds the OPT block (``served_model.opt_model``), as before.  Where
    the model's cache holds state per slot beside the pages (window
    attention layers' rings), admission still counts pages only, the
    chunk program is also told the slot and the chunk's real length, the
    decode program which lanes are live, and what the decode step counts
    (``model.count_step``) comes back with the step's one fetch into
    ``step_counters``.
    """

    def __init__(
        self,
        params,
        *,
        num_heads: Optional[int] = None,
        model: Optional[ServedModel] = None,
        batch_slots: int,
        max_seq: int,
        page_size: int = 64,
        num_pages: Optional[int] = None,
        prefill_chunk: int = 64,
        mesh=None,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        cache_dtype=None,
        rng: Optional[jax.Array] = None,
        pad_id: int = 0,
        prefix_cache: bool = True,
        capture_logits: bool = False,
        decode_kernel: str = "auto",
        host_pages: int = 0,
        tier_policy: str = "lru",
    ):
        if model is None:
            if num_heads is None:
                raise ValueError("give num_heads (the OPT block) or a model")
            _validate_model_dims(
                params, num_heads=num_heads, max_seq=max_seq, top_k=top_k
            )
            model = opt_model(params, num_heads=num_heads)
        else:
            if top_k is not None and top_k < 1:
                raise ValueError(f"top_k must be >= 1, got {top_k}")
            if model.max_positions is not None and max_seq > model.max_positions:
                raise ValueError(
                    f"max_seq {max_seq} exceeds the model's "
                    f"{model.max_positions} positions"
                )
            # refused by name, before any device work
            if prefix_cache:
                model.refuse("prefix_cache", "pass prefix_cache=False")
            if cache_dtype is not None and np.dtype(cache_dtype) == np.int8:
                model.refuse("int8_pool")
            if host_pages:
                model.refuse("host_tier")
            if mesh is not None and mesh.devices.size > 1:
                model.refuse("tensor_mesh")
        self.model = model
        num_layers = model.num_layers
        # see InferenceEngine: "flash" streams pages through
        # ops.flash_decode (in-tile int8 dequant — the QUANT_r15 speed
        # lever), "gather" is the legacy block-table-gather read
        self.decode_kernel = resolve_kernel(decode_kernel)
        self.decode_impl = flash_impl(self.decode_kernel)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.kv_layout = "paged"
        self.chunked_prefill = True
        # see InferenceEngine: what was handed in, then what is held
        self.weights_dtype = params_dtype(params)
        params = _matmul_operands(params)
        self.matmul_dtype = _matmul_dtype(params)
        self.weights_bytes = cache_bytes(params)  # any pytree's leaves
        self.params = params
        self.num_heads = num_heads
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        # the narrowest compiled chunk (the model's): a prompt's remainder
        # runs in the smallest power-of-two multiple of it that holds it
        self.prefill_chunk_floor = min(model.chunk_floor, prefill_chunk)
        self.pad_id = pad_id
        # exposed for the spec decoder's greedy-only guard
        self.temperature = float(temperature)
        self.mesh = mesh
        self.tp = (
            layout.tensor_parallel_size(mesh)
            if mesh is not None and mesh.devices.size > 1 else 1
        )
        self.layout_rules = layout.layout_rules_provenance()
        if self.tp > 1:
            if data_parallel_size(mesh) != 1:
                raise ValueError(
                    "paged engine meshes must be tensor-only (data×fsdp "
                    f"== 1): the page pool never shards; got {dict(mesh.shape)}"
                )
            if num_heads % self.tp:
                raise ValueError(
                    f"num_heads {num_heads} not divisible by the mesh's "
                    f"tensor axis ({self.tp}) — TP shards attention heads"
                )
        self.vocab_size = model.vocab_size
        if cache_dtype is None:
            cache_dtype = params["embed"].dtype
        self.kv_dtype = np.dtype(cache_dtype).name
        # fidelity-probe hook (bench.py --quant): keep the last decode
        # step's / final prefill chunk's logits host-side for comparison
        # against a reference engine — off in production (one extra
        # device->host copy per step)
        self.capture_logits = capture_logits
        self.last_logits: Optional[np.ndarray] = None
        self.last_prefill_logits: Optional[np.ndarray] = None
        # per-slot logit-finiteness verdict of the LAST decode step (the
        # scheduler's NaN-quarantine signal; same readback as the tokens)
        self.last_finite: Optional[np.ndarray] = None
        self._base_rng = jax.random.key(0) if rng is None else rng
        self._sample_step = 0

        # pages each slot can address — the static block-table width
        self.blocks_per_slot = pages_for(max_seq, page_size)
        if num_pages is None:
            # capacity parity with the dense layout; real deployments set
            # it LOWER (that is the HBM win) and let admission backpressure
            num_pages = batch_slots * self.blocks_per_slot
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        self.allocator = PageAllocator(num_pages)
        self._prefix_enabled = prefix_cache
        self._cache = model.init_cache(
            num_pages=num_pages,
            page_size=page_size,
            batch_slots=batch_slots,
            dtype=cache_dtype,
        )
        self._page_bytes = page_bytes(self._cache)
        # host page tier (serve/kv_tier.py): host_pages = 0 disables it;
        # otherwise alloc-pressure evictions demote to host instead of
        # forgetting, and the prefix walk restores host hits by DMA
        self.tier: Optional[HostPageTier] = None
        if host_pages:
            self.tier = HostPageTier(
                self._cache, host_pages, policy=tier_policy
            )
            self.allocator.set_evict_hook(self._tier_evict_hook)
        self._params_sharding = None  # reload re-places onto the same layout
        if self.tp > 1:
            # placements resolve through the partition-rule layout table:
            # weights Megatron-TP, pool head dim over tensor, page axis
            # chip-local, host plumbing (tables/offsets) replicated
            p_shard = layout.resolve_shardings(mesh, params, prefix="params")
            c_shard = cache_sharding(
                mesh, quantized=self.kv_dtype == "int8", layout="paged"
            )
            self._params_sharding = p_shard
            self.params = jax.device_put(params, p_shard)
            self._cache = jax.device_put(self._cache, c_shard)
            rep = layout.replicated(mesh)
            slot_vec = layout.io_sharding(
                mesh, "tokens", shape=(batch_slots,)
            )
            scalar = layout.io_sharding(mesh, "step", shape=())
            chunk_kw = dict(
                in_shardings=(p_shard, c_shard, rep, rep, scalar),
                out_shardings=(rep, c_shard),
            )
            decode_kw = dict(
                in_shardings=(
                    p_shard, c_shard, slot_vec, slot_vec, rep, scalar,
                    slot_vec, slot_vec,
                ),
            )
        else:
            chunk_kw = {}
            decode_kw = {}
        # host-side block tables, one row per slot; scratch-filled rows
        # make released/empty slots write into the dustbin page
        self._block_tables = np.full(
            (batch_slots, self.blocks_per_slot), SCRATCH_PAGE, np.int32
        )
        self._slot_pages: dict = {}
        # lanes whose block-table row is installed (the prompt is fully
        # written): what a model with per-slot state is told each step
        self._live = np.zeros(batch_slots, bool)
        # the tokens the last dispatched decode step sampled, still on the
        # device: the next step's input for every lane the host does not
        # set itself (see _decode_fn)
        self._last_toks = jnp.zeros(batch_slots, jnp.int32)
        # what decode steps counted since reset_stats(), under the names
        # ServeReport carries them by (empty for a model that counts none)
        self.step_counters: Dict[str, float] = {}

        # stats the scheduler/bench surface
        self.prefill_compiles = 0
        self._seen_chunk_shapes: set = set()
        self.prefix_hit_tokens = 0
        self.prompt_tokens_seen = 0
        self.pages_peak = 0
        # subset of prefix_hit_tokens answered from the HOST tier (a
        # DMA restore instead of a resident page) — the tier's win line
        self.prefix_hit_tokens_host = 0

        temperature = float(temperature)
        base_rng = self._base_rng

        def _sample(logits, step):
            return sample_logits(
                logits,
                jax.random.fold_in(base_rng, step),
                temperature=temperature,
                top_k=top_k,
            )

        dec_kernel = self.decode_kernel

        def _chunk_fn(params, cache, tokens, block_table, offset,
                      *slot_args):
            return model.prefill_chunk(
                params, tokens, cache, block_table, offset, *slot_args,
                page_size=page_size, kernel=dec_kernel, mesh=mesh,
            )

        def _decode_fn(params, cache, tokens, pos, block_tables, step,
                       prev_tokens, fresh, with_logits, *slot_args):
            # a lane's input token is the host's where the host set one
            # (``fresh``: its first token came from its prefill, or the
            # step before was read), else the one the step before sampled,
            # which never left the device
            tokens = jnp.where(fresh, tokens, prev_tokens)
            logits, cache, *counts = model.decode(
                params, tokens, cache, pos, block_tables, *slot_args,
                page_size=page_size, kernel=dec_kernel, mesh=mesh,
            )
            # per-slot health verdict (NaN quarantine) — one [slots] bool
            finite = jnp.isfinite(logits).all(axis=-1)
            # ``with_logits`` is static: the production program (False)
            # never materializes a [B, vocab] output it would discard —
            # logits stay a fusable intermediate of the sampler; the
            # probe variant (True) compiles separately on first use
            # what the step counted (model.count_step) rides in the same
            # outputs, behind the verdict: no fetch of its own
            if with_logits:
                return _sample(logits, step), logits, finite, *counts, cache
            return _sample(logits, step), finite, *counts, cache

        def _scrub_fn(cache, page_ids, from_offs, *slot_args):
            return model.scrub(
                cache, page_ids, from_offs, *slot_args, page_size=page_size
            )

        # a trace names a program after its function
        _chunk_fn.__name__ = model.programs["prefill_chunk"]
        _decode_fn.__name__ = model.programs["decode"]

        # one compiled chunk program per chunk shape (<= log2(chunk) of
        # them: full chunks plus power-of-two final-chunk buckets); all
        # tracked in the attribution registry (obs/attrib.py) per
        # layout+dtype like the dense engine's programs
        tag = f"serve.paged.{self.kv_dtype}"
        self._chunk_jit = tracked_jit(f"{tag}.prefill_chunk", jax.jit(
            _chunk_fn, donate_argnums=(1,), **chunk_kw
        ))
        self._decode_jit = tracked_jit(f"{tag}.decode", jax.jit(
            _decode_fn, donate_argnums=(1,), static_argnums=(8,), **decode_kw
        ))
        self._sample_jit = jax.jit(_sample)
        self._scrub_jit = tracked_jit(f"{tag}.scrub", jax.jit(
            _scrub_fn, donate_argnums=(0,)
        ))
        _register_engine_owners(self)
        logger.info(
            "paged engine: %d slots, %d pages x %d tokens (+scratch), %d "
            "layers, pool %.1f MB (%s), chunk %d, prefix cache %s",
            batch_slots, num_pages, page_size, num_layers,
            cache_bytes(self._cache) / 1e6, np.dtype(cache_dtype).name,
            prefill_chunk, "on" if prefix_cache else "off",
        )

    # -- accounting --------------------------------------------------------
    @property
    def cache(self):
        return self._cache

    @property
    def block_tables(self) -> np.ndarray:
        return self._block_tables

    def kernel_programs(self):
        """See :meth:`InferenceEngine.kernel_programs`."""
        if self.decode_kernel == "gather":
            return []
        return [self._chunk_jit, self._decode_jit]

    def kv_bytes(self) -> int:
        return cache_bytes(self._cache)

    def kv_bytes_peak(self) -> int:
        """Peak bytes of LIVE pages — HBM actually committed to sequences
        (the pay-per-token number the paged layout is for)."""
        return self.pages_peak * self._page_bytes

    @property
    def page_bytes_each(self) -> int:
        """Bytes one pool page holds across every leaf — the granule
        ``admit_bytes`` multiplies and the spill pump prices headroom
        in."""
        return self._page_bytes

    def prefix_hit_rate(self) -> float:
        if not self.prompt_tokens_seen:
            return 0.0
        return self.prefix_hit_tokens / self.prompt_tokens_seen

    def reset_stats(self) -> None:
        """Zero the run counters (benchmark warmup hygiene); the prefix
        TABLE survives — call ``clear_prefix_cache`` to drop that too."""
        self.prefill_compiles = 0
        self.prefix_hit_tokens = 0
        self.prompt_tokens_seen = 0
        self.pages_peak = 0
        self.prefix_hit_tokens_host = 0
        self.step_counters = {}
        if self.tier is not None:
            self.tier.reset_stats()

    def clear_prefix_cache(self) -> None:
        self.allocator.clear_prefix()
        if self.tier is not None:
            self.tier.clear()

    def chunk_shapes(self, prompt_len: int) -> set:
        """The compiled chunk widths a prompt of ``prompt_len`` will run
        (mirrors ``prefill_step``'s chunking) — warmup drivers enumerate
        these to compile every shape before the timed phase."""
        shapes = set()
        off = 0
        while off < prompt_len:
            rem = prompt_len - off
            C = self._chunk_width(rem)
            shapes.add(C)
            off += min(rem, C)
        return shapes

    def _chunk_width(self, rem: int) -> int:
        """The compiled width the next chunk of a prompt with ``rem``
        tokens left runs at: full chunks, then a power-of-two bucket (from
        the model's floor) for the remainder, which bounds the compiled
        chunk shapes."""
        if rem >= self.prefill_chunk:
            return self.prefill_chunk
        return prompt_bucket(rem, self.prefill_chunk, self.prefill_chunk_floor)

    def required_pages(self, prompt_len: int, max_new_tokens: int) -> int:
        """Pages a request needs end-to-end: its prompt plus its token
        budget, capped at the per-slot addressable window."""
        total = min(prompt_len + max_new_tokens, self.max_seq)
        return pages_for(total, self.page_size)

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Admission backpressure: pages are reserved WORST-CASE at
        admission (prompt + full budget), so decode can never strand a
        half-generated sequence out of memory mid-flight.  Conservative —
        a prefix-cache hit at ``prefill_begin`` needs fewer fresh pages."""
        return (
            self.required_pages(prompt_len, max_new_tokens)
            <= self.allocator.available
        )

    def kv_pages_held(self, written_pos: Dict[int, int]) -> Tuple[int, int]:
        """(reserved, written) pool pages right now.  ``reserved`` is
        ``allocator.pages_in_use``: distinct pages some slot holds
        (refcount >= 1; a prefix page mapped by several slots is one
        page).  ``written`` counts, of those, the distinct pages holding
        at least one written position: for each ``slot -> n`` of
        ``written_pos`` (n = positions of that slot's sequence already
        in the cache, prefix hits included), the slot's first
        ``ceil(n / page_size)`` pages.  Host bookkeeping only."""
        ps = self.page_size
        written = set()
        for slot, n in written_pos.items():
            pages = self._slot_pages.get(slot)
            if pages:
                written.update(pages[: -(-n // ps)])
        return self.allocator.pages_in_use, len(written)

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """False when the request exceeds the POOL itself — waiting for
        completions can never help; the scheduler fails it instead of
        deadlocking the queue."""
        return self.required_pages(prompt_len, max_new_tokens) <= self.num_pages

    def admit_bytes(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case committed HBM this request would add (its full
        page reservation × per-page bytes, scale leaves included) — the
        demand the scheduler's ledger forecast prices before admission.
        Conservative: a prefix-cache hit at ``prefill_begin`` commits
        fewer fresh pages."""
        return (
            self.required_pages(prompt_len, max_new_tokens)
            * self._page_bytes
        )

    def _next_step(self) -> int:
        step = self._sample_step
        self._sample_step += 1
        return step

    # -- prefill -----------------------------------------------------------
    def _prefix_key(self, prompt, n_pages: int):
        # key = full token history through the end of page n — a hit
        # guarantees the page holds exactly prefill's K/V for those tokens
        return tuple(prompt[: n_pages * self.page_size])

    def prefill_begin(
        self, slot: int, prompt: Sequence[int], max_new_tokens: int,
        uid: Optional[str] = None,
    ) -> PrefillTask:
        """Allocate the sequence's pages (prefix-cache hits first), map
        the slot's block table, and return the chunking task (``uid``, the
        request's, names its spans)."""
        length = len(prompt)
        if not length:
            raise ValueError("empty prompt")
        if length >= self.max_seq:
            raise ValueError(
                f"prompt length {length} leaves no room to generate "
                f"(max_seq {self.max_seq})"
            )
        if not 0 <= slot < self.batch_slots:
            raise ValueError(
                f"slot {slot} out of range [0, {self.batch_slots})"
            )
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} still holds pages — release first")
        ps = self.page_size
        n_total = self.required_pages(length, max_new_tokens)

        # prefix reuse: walk the chain of FULL prompt pages.  Capped at
        # length-1 tokens so at least the last prompt token always runs
        # through prefill — its logits seed the first sampled token.
        # The prefix table answers in EITHER tier: a resident hit maps
        # the page, a host hit allocates a fresh page and dispatches the
        # async restore into it (prefetch-aware prefill — the chunk
        # program consuming the page orders after the H2D transfer, so
        # no explicit wait sits on this path).
        shared: list = []
        restored = 0
        if self._prefix_enabled:
            max_shared = (length - 1) // ps
            for i in range(max_shared):
                key = self._prefix_key(prompt, i + 1)
                page = self.allocator.lookup_prefix(key)
                if (
                    page is None
                    and self.tier is not None
                    and self.allocator.tier_state(key) == "host"
                ):
                    page = self._prefetch_page(key)
                    if page is not None:
                        restored += 1
                if page is None:
                    break
                shared.append(page)
        for p in shared:
            self.allocator.incref(p)
        try:
            fresh = self.allocator.alloc(n_total - len(shared))
        except OutOfPages:
            for p in shared:  # roll the hit refs back before backpressure
                self.allocator.decref(p)
            raise
        pages = shared + fresh
        self._slot_pages[slot] = pages
        # The slot's _block_tables row stays SCRATCH until the final chunk
        # lands (prefill_step installs it): decode steps run WHILE this
        # slot is mid-prefill, and every decode lane writes unconditionally
        # — with the real row installed, the stale lane's (pos 0) write
        # would corrupt the prompt's already-written K/V or a SHARED
        # prefix page.  The chunk program gets a task-local table instead.
        self.pages_peak = max(self.pages_peak, self.allocator.pages_in_use)
        offset = len(shared) * ps
        self.prompt_tokens_seen += length
        self.prefix_hit_tokens += offset
        self.prefix_hit_tokens_host += restored * ps
        return PrefillTask(slot, prompt, pages, offset, offset, uid)

    def prefill_step(self, task: PrefillTask) -> Optional[int]:
        """Run ONE chunk of ``task``'s prompt; returns the first sampled
        continuation token when the final chunk completes, else None."""
        if task.done:
            raise ValueError("prefill task already complete")
        length = len(task.prompt)
        rem = length - task.offset
        C = self._chunk_width(rem)
        real = min(rem, C)
        if C not in self._seen_chunk_shapes:
            self._seen_chunk_shapes.add(C)
            self.prefill_compiles += 1
        tokens = np.full((1, C), self.pad_id, np.int32)
        tokens[0, :real] = np.asarray(
            task.prompt[task.offset : task.offset + real], np.int32
        )
        # task-local block table: the slot's shared row is still SCRATCH
        # (see prefill_begin) so interleaved decode steps can't touch
        # these pages until the prompt is fully written
        table = np.full(self.blocks_per_slot, SCRATCH_PAGE, np.int32)
        table[: len(task.pages)] = task.pages
        slot_args = (
            (jnp.int32(task.slot), jnp.int32(real))
            if self.model.slot_state else ()
        )
        with get_tracer().span(
            "serve/engine.chunk_dispatch", chunk=C, offset=task.offset
        ):
            logits, self._cache = self._chunk_jit(
                self.params,
                self._cache,
                jnp.asarray(tokens),
                jnp.asarray(table),
                jnp.int32(task.offset),
                *slot_args,
            )
        chunk_start = task.offset
        task.offset += real
        # publish freshly completed FULL prompt pages for prefix reuse —
        # immediately, so same-wave requests sharing the prefix hit too
        if self._prefix_enabled:
            first_new = chunk_start // self.page_size
            last_full = min(task.offset, length) // self.page_size
            for i in range(first_new, last_full):
                key = self._prefix_key(task.prompt, i + 1)
                if (
                    self.tier is not None
                    and self.allocator.tier_state(key) == "host"
                ):
                    # this chunk just recomputed the page (the walk stops
                    # before the final prompt page, so its host copy was
                    # unreachable there) — the fresh resident page
                    # supersedes the bit-identical host copy
                    self.tier.drop(key)
                    self.allocator.drop_host(key)
                self.allocator.register_prefix(key, task.pages[i])
        if not task.done:
            return None
        # prompt fully written: NOW the slot's decode row may see the pages
        self._block_tables[task.slot] = SCRATCH_PAGE
        self._block_tables[task.slot, : len(task.pages)] = task.pages
        self._live[task.slot] = True
        last = jax.lax.dynamic_index_in_dim(
            logits, 0 if self.model.last_logits_only else real - 1,
            axis=1, keepdims=False,
        )  # [1, vocab] — last REAL position of the final chunk
        if self.capture_logits:
            self.last_prefill_logits = np.asarray(last)[0]
        tok = self._sample_jit(last, jnp.int32(self._next_step()))
        # the turn's one blocking read besides decode_fetch: the chunk
        # program and the sampler, behind whatever step is in flight
        with get_tracer().span(
            "serve/engine.first_token_fetch", uid=task.uid, slot=task.slot
        ):
            return int(np.asarray(tok)[0])

    def prefill(
        self,
        slot: int,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
    ) -> int:
        """Monolithic convenience: run every chunk back-to-back (API
        parity with the dense engine for tests/direct use; the scheduler
        interleaves ``prefill_step`` with decode instead).  Without a
        budget the slot reserves through ``max_seq`` — dense-equivalent
        worst case."""
        if max_new_tokens is None:
            max_new_tokens = self.max_seq - len(prompt)
        task = self.prefill_begin(slot, prompt, max_new_tokens)
        while True:
            tok = self.prefill_step(task)
            if tok is not None:
                return tok

    # -- decode / release --------------------------------------------------
    def decode(self, tokens: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """One decode step for every slot via block-table gather.  Same
        contract as the dense engine; released slots' rows point at the
        scratch page so their (ignored) lane writes are harmless.  The
        step's two halves back to back."""
        return self.decode_fetch(self.decode_dispatch(tokens, pos))

    # what a loop asks before it calls the halves itself: an engine whose
    # ``decode`` was wrapped or overridden (a subclass, a planted fault) is
    # driven through that ``decode``
    decode.is_its_two_halves = True

    def decode_dispatch(
        self,
        tokens: np.ndarray,
        pos: np.ndarray,
        fresh: Optional[np.ndarray] = None,
        rows: Optional[np.ndarray] = None,
    ) -> "DispatchedStep":
        """Upload one decode step's arguments and launch it; read nothing.

        ``fresh[i]`` false makes lane ``i``'s input the token the step
        dispatched before this one sampled for it, taken where it lies on
        the device (``tokens[i]`` is then ignored), so a caller can launch
        step n+1 before it has read step n.  ``rows[i]`` false gives lane
        ``i`` what a released slot has: the scratch row, not live; for a
        lane that the caller knows to have ended but has not released yet.
        Both default to every lane.  What comes back goes to
        :meth:`decode_fetch`, once, in dispatch order.  Nothing here keeps
        a reference to the caller's buffers: they may change as soon as
        this returns."""
        trace = get_tracer()
        step = self._next_step()
        with trace.span("serve/engine.decode_upload", step=step):
            tables, live = self._block_tables, self._live
            if rows is not None:
                tables = np.where(rows[:, None], tables, SCRATCH_PAGE)
                live = live & rows
            # host copies that nothing else holds: an upload may alias
            # its source (it does on the CPU), and the step may still run
            # when the caller, or release / prefill_step here, next writes
            # these buffers
            args = (
                self.params,
                self._cache,
                jnp.asarray(np.array(tokens, np.int32)),
                jnp.asarray(np.array(pos, np.int32)),
                jnp.asarray(np.array(tables)),
                jnp.int32(step),
                self._last_toks,
                jnp.asarray(
                    np.ones(self.batch_slots, bool) if fresh is None
                    else np.array(fresh)),
            )
            slot_args = (
                (jnp.asarray(np.array(live)),)
                if self.model.slot_state else ()
            )
        logits = None
        with trace.span("serve/engine.decode_dispatch", step=step):
            if self.capture_logits:
                toks, logits, finite, *counts, self._cache = (
                    self._decode_jit(*args, True, *slot_args)
                )
            else:
                toks, finite, *counts, self._cache = self._decode_jit(
                    *args, False, *slot_args
                )
        self._last_toks = toks
        return DispatchedStep(
            step, toks, finite, logits, counts[0] if counts else None,
            np.asarray(pos)[live] if counts else None,
        )

    def decode_fetch(self, step: "DispatchedStep") -> np.ndarray:
        """Read a dispatched step: its sampled tokens come back,
        ``last_finite`` (and ``last_logits``) are then that step's, and
        what it counted is added to ``step_counters``."""
        trace = get_tracer()
        # every device->host read in ONE span of its own (same contract
        # as the dense engine): the logits probe must not be billed to
        # dispatch, or the dispatch-vs-readback split on the timeline
        # reads as ~0 exactly when capture_logits is on
        with trace.span("serve/engine.decode_fetch", step=step.step):
            if step.logits is not None:
                self.last_logits = np.asarray(step.logits)
            self.last_finite = np.asarray(step.finite)
            toks = np.asarray(step.tokens)
            if step.counts is not None:
                # what the model's step counted: its own reducer names the
                # report's fields, the engine only adds
                counted = self.model.count_step(
                    np.asarray(step.counts), step.live_pos)
                for name, value in counted.items():
                    self.step_counters[name] = (
                        self.step_counters.get(name, 0) + value)
                # the step's own addends at the step's time, so that a
                # traced window can be counted by itself (recorded only
                # while the tracer is on or a capture is live)
                trace.event("serve/engine.step_counts", **counted)
            return toks

    # -- fault injection / quarantine hooks --------------------------------
    def poison_slot(self, slot: int, pos: int) -> None:
        """Corrupt ``slot``'s K history at logical position ``pos`` with
        NaN (the ``decode_nan`` fault).  K only — see the dense engine's
        docstring for why a NaN *value* would leak through masking.

        The caller must pass a DECODE-WRITTEN position (>= the delivery's
        prompt length): pages covering those positions are never in the
        prefix table (only full *prompt* pages register), so the poison
        can only ever land in a page private to this slot."""
        pages = self._slot_pages.get(slot)
        if not pages:
            raise ValueError(f"slot {slot} holds no pages to poison")
        page = pages[pos // self.page_size]
        off = pos % self.page_size
        self._cache = self.model.poison(self._cache, page, off)

    def scrub_slot(self, slot: int, from_pos: int = 0) -> None:
        """Zero the slot's cache from logical position ``from_pos`` on,
        POSITION-granular: within the boundary page only offsets
        ``>= from_pos % page_size`` are zeroed, so positions
        ``< from_pos`` survive bit-exact — the rollback primitive
        speculative decoding's rejected tails go through, and the NaN
        quarantine's cleanup (``from_pos`` = the delivery's prompt
        length scrubs exactly the decode-written region).

        Prefix-SHARED pages are never written: every touched page must be
        private to this slot (refcount 1, unpublished) — with ``from_pos
        >=`` the shared-prefix length that holds by construction (shared
        pages only ever cover full prompt pages below it), and a caller
        that would violate it gets a loud error instead of corrupting
        other slots' history.  One compiled program serves every
        (slot, from_pos)."""
        pages = self._slot_pages.get(slot, [])
        if not pages:
            return
        ps = self.page_size
        start = from_pos // ps
        if start >= len(pages):
            return
        shared = [
            p for p in pages[start:] if self.allocator.is_shared(p)
        ]
        if shared:
            raise ValueError(
                f"scrub_slot(slot={slot}, from_pos={from_pos}) would "
                f"write prefix-shared page(s) {shared} — shared pages "
                "are immutable; scrub only from the private region on"
            )
        ids = np.full(self.blocks_per_slot, SCRATCH_PAGE, np.int32)
        offs = np.full(self.blocks_per_slot, ps, np.int32)  # ps = no-op
        for idx in range(start, len(pages)):
            ids[idx] = pages[idx]
            offs[idx] = max(0, from_pos - idx * ps)
        slot_args = (jnp.int32(slot),) if self.model.slot_state else ()
        self._cache = self._scrub_jit(
            self._cache, jnp.asarray(ids), jnp.asarray(offs), *slot_args
        )

    def release(self, slot: int) -> None:
        """Return the slot's pages to the pool.  Prefix-registered pages
        drop to the reclaimable LRU (future hits resurrect them); private
        pages go straight back to the free list.  Per-slot state (a window
        layer's ring) is dropped from view with them: the lane stops being
        live, and the next occupant's positions mask whatever the ring
        still holds."""
        for page in self._slot_pages.pop(slot, []):
            self.allocator.decref(page)
        self._block_tables[slot] = SCRATCH_PAGE
        self._live[slot] = False

    # -- host page tier ----------------------------------------------------
    def _tier_evict_hook(self, key, page: int) -> bool:
        """Alloc-pressure demotion (installed on the allocator): copy the
        about-to-be-recycled reclaimable page host-side so its key keeps
        answering prefix hits.  False (eviction forgets the key) only
        when the host pool can take nothing right now."""
        evicted = self.tier.spill_in(self._cache, key, page)
        if evicted is None:
            return False
        for k in evicted:
            self.allocator.drop_host(k)
        return True

    def _prefetch_page(self, key):
        """Restore a host-tier prefix chunk into a fresh HBM page:
        allocate, dispatch the async H2D transfer, commit the page into
        the pool, and hand ownership to the prefix table (refcount 0 →
        reclaimable, exactly like a resident prefix page; the caller's
        incref takes the slot's reference).  None when the pool has no
        page for it — the walk stops and the tail re-prefills."""
        try:
            (page,) = self.allocator.alloc(1)
        except OutOfPages:
            return None
        dev = self.tier.dispatch_restore(key)
        c = dict(self._cache)
        for name, leaf in dev.items():
            c[name] = c[name].at[page].set(leaf)
        self._cache = c
        self.allocator.restore_prefix(key, page)
        self.allocator.decref(page)
        return page

    def spill_cold_pages(self, max_pages: int) -> int:
        """The spill pump's primitive: demote up to ``max_pages`` LRU
        reclaimable prefix pages to the host tier, returning their HBM
        pages to the free list.  Returns pages actually spilled.  Only
        refcount-0 pages are candidates — a decode-active page is never
        spilled (its bytes are in flight on device this iteration)."""
        if self.tier is None or max_pages <= 0:
            return 0
        spilled = 0
        for key, page in self.allocator.coldest_reclaimable(max_pages):
            evicted = self.tier.spill_in(self._cache, key, page)
            if evicted is None:
                break
            for k in evicted:
                self.allocator.drop_host(k)
            self.allocator.spill_prefix(key)
            spilled += 1
        return spilled

    def spill_slot_pages(self, slot: int, tokens: Sequence[int]) -> int:
        """Preemption-resume path: demote the slot's PRIVATE full pages
        to the host tier keyed by their token history (``tokens`` =
        prompt + generated so far), so the retry's prefix walk restores
        them by DMA instead of re-prefilling.  Pages already answering
        in either tier (shared prompt prefixes) are skipped — they
        survive preemption on their own.  Call BEFORE ``release``:
        the copies need the pages still mapped and unrecycled."""
        if self.tier is None:
            return 0
        pages = self._slot_pages.get(slot, [])
        ps = self.page_size
        n_full = min(len(tokens) // ps, len(pages))
        spilled = 0
        for i in range(n_full):
            key = self._prefix_key(tokens, i + 1)
            if self.allocator.tier_state(key) is not None:
                continue
            if self.allocator.is_shared(pages[i]):
                continue
            evicted = self.tier.spill_in(self._cache, key, pages[i])
            if evicted is None:
                break
            for k in evicted:
                self.allocator.drop_host(k)
            self.allocator.host_prefix(key)
            spilled += 1
        return spilled

    def tier_inflight(self) -> int:
        """Retire landed prefetches; how many H2D restores are still in
        flight (the scheduler's admit gate polls this)."""
        return 0 if self.tier is None else self.tier.poll()

    def drain_tier(self) -> None:
        """Fence every in-flight prefetch (blocking) — the admission
        gate's last resort before it would preempt a victim."""
        if self.tier is not None:
            self.tier.drain()

    # -- live weight reload ------------------------------------------------
    def reload_params(self, params) -> None:
        """Swap the engine's weight set IN PLACE (see the dense engine's
        docstring for the same-avals contract — compiled programs and the
        page pool stay untouched — and for what becomes of a float32 tree
        where the engine serves a bf16 copy of its matmul weights).

        Paged extras: refuses while any slot holds pages (a live slot
        spanning the swap would decode new-weight queries against
        old-weight K/V — the scheduler's idle barrier guarantees this
        never happens in serving), and DROPS the prefix table — cached
        prefix pages hold K/V computed by the OLD weights, and a
        post-reload hit on them would silently break the fresh-engine
        bit-exactness contract."""
        if self._slot_pages:
            raise ValueError(
                "reload_params with live slots "
                f"{sorted(self._slot_pages)} — reload is a barrier "
                "between requests; drain the slots first (the scheduler's "
                "request_reload does)"
            )
        params = _matmul_operands(params)
        _check_reload_tree(self.params, params)
        if self._params_sharding is not None:
            params = jax.device_put(params, self._params_sharding)
        self.params = params
        self.allocator.clear_prefix()
        # host-tier pages hold OLD-weight K/V too — a post-reload restore
        # of one would break fresh-engine bit-exactness just as surely as
        # a resident stale prefix page
        if self.tier is not None:
            self.tier.clear()
        logger.info(
            "paged engine: params reloaded in place, prefix cache dropped"
        )
