"""Causal-LM transformer workload — the pipeline-parallel (`pipe`) consumer.

The reference has no pipeline parallelism or LM workload at all (Horovod DP
over CNNs only — SURVEY.md §2 "Parallelism strategies"); this workload makes
the framework's sixth mesh axis launchable end-to-end:

    ddlt transformer submit local synthetic --pipe 2 --num_microbatches 8

trains :mod:`models.pipelined_transformer` — a stack of identical pre-LN
blocks with parameters stacked ``[L, ...]`` — with the stages GPipe-scheduled
over the ``pipe`` axis (:func:`ops.pipeline.pipeline_apply`), driven by the
SAME Trainer/checkpoint/metrics machinery as every other workload: the
stacked-param pytree rides an ordinary ``TrainState``, the stage dim shards
over ``pipe`` via a one-rule logical-axis tree, and orbax checkpoints/resume
work unchanged.  ``--pipe 1`` degrades to a plain scan-over-layers LM, so the
workload also serves as the framework's single-chip LM trainer.

Data is synthetic next-token streams (the LM analogue of the reference's
synthetic benchmark mode, ``data/synthetic.py:4-52``): fixed-seed random
token sequences, loss = shifted cross-entropy.
"""

from __future__ import annotations

import logging
from typing import Iterator, Optional

logger = logging.getLogger("ddlt.workloads.transformer")


def _token_batches(
    per_host_batch: int,
    seq_len: int,
    vocab_size: int,
    seed: int,
    length: int,
    repeat: bool,
) -> Iterator:
    """Deterministic synthetic LM batches: {"input": toks, "label": toks}.

    The label IS the input — the causal shift happens inside the loss
    (models/pipelined_transformer.next_token_loss)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n_batches = max(length // per_host_batch, 1)
    epoch = [
        rng.integers(0, vocab_size, (per_host_batch, seq_len)).astype(np.int32)
        for _ in range(n_batches)
    ]
    while True:
        for toks in epoch:
            yield {"input": toks, "label": toks}
        if not repeat:
            return


def main(
    *,
    epochs: int = 3,
    batch_size: int = 8,  # per chip
    seq_len: int = 128,
    vocab_size: int = 1031,
    num_layers: int = 8,
    d_model: int = 256,
    num_heads: int = 8,
    d_ff: int = 1024,
    base_lr: float = 3e-4,
    warmup_fraction: float = 0.1,
    weight_decay: float = 0.01,
    grad_clip_norm: float = 1.0,
    accum_steps: int = 1,
    train_examples: Optional[int] = None,
    steps_per_epoch: Optional[int] = None,
    save_filepath: Optional[str] = None,
    tensorboard_dir: Optional[str] = None,
    resume: bool = True,
    profile_dir: Optional[str] = None,
    metrics_path: Optional[str] = None,
    checkpoint_every_steps: Optional[int] = None,  # mid-epoch save cadence
    seed: int = 42,
    compute_dtype: str = "bfloat16",
    distributed: Optional[bool] = None,
    data_format: str = "synthetic",  # LM data is synthetic-only (see module doc)
    # parallelism geometry: pipeline × sequence × fsdp × data (remainder)
    pipe: int = 1,
    seq: int = 1,  # sequence-parallel axis (ring / ulysses attention)
    # ZeRO-3-style parameter sharding: embed/head shard their vocab dim,
    # qkv/proj/FF their width dims, over the fsdp axis (batch shards over
    # it too).  Requires vocab_size, d_model and d_ff divisible by fsdp.
    fsdp: int = 1,
    # Megatron-style tensor parallelism: the SAME width dims shard over
    # the tensor axis instead (batch does NOT shard over it, so XLA emits
    # row-parallel activation all-reduces rather than param all-gathers).
    # Composes with fsdp (vocab stays on fsdp) and pipe.
    tensor: int = 1,
    num_slices: int = 1,  # multi-slice (DCN) data parallelism
    num_microbatches: int = 8,
    # jax.checkpoint each pipeline tick (pipe>1, ops/pipeline.py) or each
    # layer of the sequential scan (pipe=1) — the long-context memory lever
    remat: bool = False,
    # fuse head matmul + CE over sequence chunks so the [b,s,vocab] f32
    # logits never materialize (models.per_token_loss; must divide
    # seq_len-1).  top1 is unavailable in this mode (no logits exist).
    loss_chunk: Optional[int] = None,
    # lax.scan unroll factor for the layer stack: removes scan-carry
    # dynamic-update-slice traffic from the backward (LM_FLASH_r05: best at
    # short seq; keep 1 at long context -- the unrolled scan holds more
    # live buffers and seq-64k OOMs at 12)
    scan_unroll: int = 1,
    # "flash" = causal Pallas kernel (long context, single shard);
    # "ring"/"ulysses" = causal sequence-parallel attention over --seq
    attention: str = "dense",
    # ring attention's blocked inner loop: bounds per-tick score memory at
    # O(Sq*block_k) — set for long-context launches (must divide S/seq)
    sp_block_k: Optional[int] = None,
    # -- explicit gradient comms (parallel/comms.py; step.py docstrings);
    # pure-DP geometry only (pipe/seq/fsdp/tensor all 1) --
    comm_overlap: bool = False,  # bucketed reduce-scatter overlap schedule
    bucket_mb: float = 4.0,  # gradient bucket size for comm_overlap
    comm_dtype: Optional[str] = None,  # "bf16" = compressed wire + error feedback
    weight_update_sharding: bool = False,  # ZeRO distributed optimizer
    # -- resilience (train/resilience.py; see TrainerConfig docstrings) --
    skip_nonfinite: bool = False,  # in-step guard: discard non-finite updates
    anomaly_max_consecutive: Optional[int] = None,  # abort after N in a row
    anomaly_rollback: bool = False,  # restore last ckpt instead of aborting
    step_deadline_s: Optional[float] = None,  # watchdog: stacks + exit 70
):
    """Train; returns (state, FitResult)."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
        forward_pipelined,
        init_params,
        next_token_loss,
        per_token_loss,
    )
    from distributeddeeplearning_tpu.parallel import (
        MeshSpec,
        create_mesh,
        initialize,
    )
    from distributeddeeplearning_tpu.train.loop import Trainer, TrainerConfig
    from distributeddeeplearning_tpu.train.schedule import (
        warmup_linear_decay_schedule,
    )
    from distributeddeeplearning_tpu.train.state import TrainState, adamw
    from distributeddeeplearning_tpu.train.step import (
        build_eval_step,
        build_train_step,
        place_state,
        topk_correct,
    )

    if data_format != "synthetic":
        raise ValueError(
            "the transformer LM workload is synthetic-data only "
            f"(got data_format={data_format!r})"
        )
    if num_layers % max(pipe, 1):
        raise ValueError(
            f"num_layers {num_layers} not divisible by pipe {pipe}"
        )
    # Sequence parallelism: the SP attention ops shard_map over the mesh
    # themselves, which cannot nest inside the pipeline's shard_map — the
    # two long-context axes compose with data parallelism, not each other.
    _sp_modes = ("ring", "ulysses", "ulysses-flash")
    if pipe > 1 and (seq > 1 or attention in _sp_modes):
        raise ValueError(
            "pipe and sequence parallelism are mutually exclusive: the "
            "sequence-parallel attention cannot run inside a pipeline stage"
        )
    if seq > 1 and attention not in _sp_modes:
        raise ValueError(
            f"seq={seq} requires attention='ring', 'ulysses' or "
            f"'ulysses-flash', got {attention!r}"
        )
    if attention in _sp_modes and seq_len % max(seq, 1):
        raise ValueError(f"seq_len {seq_len} not divisible by seq axis {seq}")
    if loss_chunk and pipe > 1:
        raise ValueError(
            "loss_chunk uses the sequential forward and cannot combine "
            "with pipe > 1"
        )
    if scan_unroll > 1 and pipe > 1:
        raise ValueError(
            "scan_unroll applies to the sequential scan-over-layers only "
            "and has no effect inside pipeline stages; drop it or pipe"
        )
    if fsdp > 1 and (
        vocab_size % fsdp or d_model % fsdp or d_ff % fsdp
    ):
        raise ValueError(
            f"fsdp={fsdp} must divide vocab_size ({vocab_size}), "
            f"d_model ({d_model}) and d_ff ({d_ff})"
        )
    if tensor > 1 and (
        d_model % tensor or d_ff % tensor or num_heads % tensor
    ):
        raise ValueError(
            f"tensor={tensor} must divide d_model ({d_model}), "
            f"d_ff ({d_ff}) and num_heads ({num_heads})"
        )
    if comm_overlap:
        if pipe > 1 or seq > 1 or fsdp > 1 or tensor > 1:
            raise ValueError(
                "comm_overlap is the explicit replicated-params DP "
                "schedule; it does not compose with pipe/seq/fsdp/tensor"
            )
        if weight_update_sharding and grad_clip_norm:
            raise ValueError(
                "weight_update_sharding applies the optimizer per gradient "
                "shard, so optax.clip_by_global_norm would clip by the "
                "SHARD norm — pass --grad_clip_norm 0 with "
                "--weight_update_sharding"
            )
    ctx = initialize(force=distributed)
    mesh = create_mesh(
        MeshSpec(pipe=pipe, seq=seq, fsdp=fsdp, tensor=tensor),
        num_slices=num_slices,
    )
    attention_fn = None
    if attention == "ring":
        from distributeddeeplearning_tpu.ops import make_ring_attention

        attention_fn = make_ring_attention(
            mesh, causal=True, block_k=sp_block_k
        )
    elif attention in ("ulysses", "ulysses-flash"):
        from distributeddeeplearning_tpu.ops import make_ulysses_attention

        attention_fn = make_ulysses_attention(
            mesh, causal=True, use_flash=attention == "ulysses-flash"
        )
    elif attention == "flash" and pipe == 1 and mesh.devices.size > 1:
        # A bare pallas_call cannot be partitioned by GSPMD — on a
        # multi-chip mesh the kernel must run per-shard inside shard_map
        # (batch over data/fsdp, heads over tensor) or every chip gathers
        # the global batch.  Inside a pipeline stage (pipe > 1) the
        # pipeline's own shard_map already scopes it, so only the
        # sequential forward needs the wrap.
        from distributeddeeplearning_tpu.ops import make_flash_attention

        attention_fn = make_flash_attention(mesh=mesh, causal=True)
    data_shards = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = batch_size * data_shards
    per_host_batch = global_batch // ctx.process_count
    if pipe > 1 and (global_batch // data_shards) % num_microbatches:
        raise ValueError(
            f"per-data-shard batch {global_batch // data_shards} not "
            f"divisible by num_microbatches {num_microbatches}"
        )
    dtype = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32

    n_train = train_examples or 25_000
    spe = steps_per_epoch or max(n_train // global_batch, 1)
    total_steps = spe * epochs

    if ctx.is_primary:
        logger.info(
            "training %d-layer LM: %d chips (pipe=%d data=%d), global batch "
            "%d, %d microbatches, %d steps/epoch, %d epochs",
            num_layers, mesh.devices.size, pipe, mesh.shape["data"],
            global_batch, num_microbatches if pipe > 1 else 1, spe, epochs,
        )

    params = init_params(
        jax.random.key(seed),
        num_layers=num_layers,
        d_model=d_model,
        num_heads=num_heads,
        d_ff=d_ff,
        vocab_size=vocab_size,
        max_len=seq_len,
    )

    def apply_fn(variables, tokens, train=True, mutable=None, rngs=None):
        p = jax.tree_util.tree_map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            variables["params"],
        )
        if loss_chunk:
            # "logits" are the per-position losses [b, s-1]; the full
            # [b, s, vocab] f32 logits never materialize.
            out = per_token_loss(
                p, tokens, num_heads=num_heads, attention=attention,
                attention_fn=attention_fn, remat=remat,
                loss_chunk=loss_chunk, unroll=scan_unroll,
            )
        elif pipe > 1:
            # pipe×fsdp: ZeRO-3 width shards live inside the pipeline
            # stages (gathered per tick); with --tensor the width dims
            # belong to the tensor axis instead and GSPMD handles the
            # boundary resharding.
            out = forward_pipelined(
                p, tokens, num_heads=num_heads, mesh=mesh,
                num_microbatches=num_microbatches, remat=remat,
                attention=attention,
                zero3_axis="fsdp" if fsdp > 1 and tensor == 1 else None,
            ).astype(jnp.float32)
        else:
            out = forward(p, tokens, num_heads=num_heads,
                          attention=attention, attention_fn=attention_fn,
                          remat=remat,
                          unroll=scan_unroll).astype(jnp.float32)
        if mutable is not None:
            return out, {}
        return out

    schedule = warmup_linear_decay_schedule(
        base_lr, total_steps, warmup_fraction=warmup_fraction
    )
    tx = adamw(
        schedule, weight_decay=weight_decay, grad_clip_norm=grad_clip_norm
    )
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        batch_stats={},
        apply_fn=apply_fn,
        tx=tx,
    )

    # The stacked layer dim shards over pipe (contiguous stages — exactly
    # the [S, L/S] reshape forward_pipelined performs); the vocab dim
    # shards over fsdp; the width dims shard over tensor when --tensor > 1
    # (Megatron TP: batch not sharded over it → row-parallel activation
    # all-reduces) and over fsdp otherwise (ZeRO: batch sharded over it →
    # param all-gathers).  Everything is a no-op at axis size 1, so the
    # pure-pipe and pure-DP geometries are unchanged.
    width_axis = "tensor" if tensor > 1 else "fsdp"
    rules = [("layers", "pipe"), ("vocab", "fsdp"), ("width", width_axis)]
    logical_axes = {
        "embed": ("vocab", None),          # [V, D]
        "pos": None,
        "head": (None, "vocab"),           # [D, V]
        "blocks": {
            "qkv": ("layers", None, "width"),    # [L, D, 3D]
            "proj": ("layers", "width", None),   # [L, D, D]
            "w_in": ("layers", None, "width"),   # [L, D, FF]
            "w_out": ("layers", "width", None),  # [L, FF, D]
            "ln1": ("layers", None),
            "ln2": ("layers", None),
        },
    }

    if loss_chunk:
        # apply_fn already returned per-position losses; no logits exist,
        # so top1 is structurally unavailable in this mode.
        def lm_loss(losses, labels, *, label_smoothing: float = 0.0):
            del label_smoothing
            return losses.mean()

        def lm_metrics(losses, tokens, loss):
            return {
                "loss": loss.astype(jnp.float32),
                "perplexity": jnp.exp(loss).astype(jnp.float32),
            }
    else:
        def lm_loss(logits, labels, *, label_smoothing: float = 0.0):
            del label_smoothing  # the LM loss has no smoothing knob
            return next_token_loss(logits, labels)

        def lm_metrics(logits, tokens, loss):
            # the shifted logits stay 3-D, as in next_token_loss:
            # flattening the non-contiguous slice to [b·(s-1), V] is a
            # 1 GB compaction copy at the full LM width, and cost the TPU
            # compiler three minutes of the train step's compile
            return {
                "loss": loss.astype(jnp.float32),
                "top1": topk_correct(logits[:, :-1], tokens[:, 1:], 1),
                "perplexity": jnp.exp(loss).astype(jnp.float32),
            }

    train_step = build_train_step(
        mesh, state, schedule=schedule, compute_dtype=dtype,
        # comm_overlap is replicated-params only: the rules exist for the
        # pipe/fsdp/tensor geometries this mode already excluded above
        rules=None if comm_overlap else rules,
        logical_axes=None if comm_overlap else logical_axes,
        loss_fn=lm_loss, metrics_fn=lm_metrics,
        rng=jax.random.key(seed + 1), accum_steps=accum_steps,
        skip_nonfinite=skip_nonfinite,
        comm_overlap=comm_overlap, bucket_mb=bucket_mb,
        comm_dtype=comm_dtype,
        weight_update_sharding=weight_update_sharding,
    )
    if comm_overlap:
        # prepared state doubles as the checkpoint restore template
        state = train_step.prepare_state(state)
    else:
        # so does the placed state: a resume restores into these shards
        state = place_state(
            mesh, state, rules=rules, logical_axes=logical_axes
        )
    eval_step = build_eval_step(
        mesh, state, compute_dtype=dtype, rules=rules,
        logical_axes=logical_axes, loss_fn=lm_loss, metrics_fn=lm_metrics,
    )

    train_iter = _token_batches(
        per_host_batch, seq_len, vocab_size, seed + ctx.process_index,
        n_train, repeat=True,
    )

    def eval_factory():
        return _token_batches(
            per_host_batch, seq_len, vocab_size,
            seed + 7000 + ctx.process_index,
            min(n_train, 4 * global_batch), repeat=False,
        )

    trainer = Trainer(
        mesh,
        train_step,
        eval_step=eval_step,
        config=TrainerConfig(
            epochs=epochs,
            steps_per_epoch=spe,
            global_batch_size=global_batch,
            checkpoint_dir=save_filepath,
            tensorboard_dir=tensorboard_dir,
            resume=resume,
            profile_dir=profile_dir,
            metrics_path=metrics_path,
            checkpoint_every_steps=checkpoint_every_steps,
            anomaly_max_consecutive=anomaly_max_consecutive,
            anomaly_rollback=anomaly_rollback,
            step_deadline_s=step_deadline_s,
        ),
    )
    return trainer.fit(state, train_iter, eval_factory)


if __name__ == "__main__":
    import logging as _logging

    _logging.basicConfig(level=_logging.INFO)
    from distributeddeeplearning_tpu.workloads._runner import run_from_argv

    run_from_argv(main)
