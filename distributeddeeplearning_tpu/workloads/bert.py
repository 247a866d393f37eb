"""BERT fine-tune workload — sequence classification at pod scale.

The reference has no transformer workload; BASELINE.md tracks "BERT-base
fine-tune pod-scale DP" as a target config and the framework treats
long-context/distributed attention as first-class.  This driver fine-tunes
:class:`models.bert.BertEncoder` on tokenized text:

- inputs: synthetic tokens (``data.synthetic.SyntheticTextDataset``) or
  pre-tokenized TFRecord shards (``data.text``), host-sharded like every
  other pipeline;
- optimizer: AdamW + global-norm clip, linear warmup → linear decay
  (the Devlin et al. fine-tuning recipe);
- parallelism: ``--fsdp/--tensor/--seq`` flags shape the mesh (the data
  axis absorbs the remaining devices).
  fsdp/tp shard params via the logical-axis rules; ``--seq > 1`` swaps the
  attention primitive for :func:`ops.ring_attention` so sequence blocks
  rotate around the ICI ring — the long-context path;
- launchable via ``python -m distributeddeeplearning_tpu.workloads.bert``
  or ``ddlt bert submit {local,remote} {synthetic,tfrecords}``.
"""

from __future__ import annotations

import logging
from typing import Iterator, Optional

logger = logging.getLogger("ddlt.workloads.bert")


def _batches(
    data_format: str,
    data_path: Optional[str],
    is_training: bool,
    per_host_batch: int,
    seq_len: int,
    vocab_size: int,
    num_classes: int,
    seed: int,
    synthetic_length: Optional[int] = None,
) -> Iterator:
    if data_format == "synthetic":
        import jax

        from distributeddeeplearning_tpu.data.synthetic import SyntheticTextDataset

        ds = SyntheticTextDataset(
            length=synthetic_length,
            seq_len=seq_len,
            vocab_size=vocab_size,
            num_classes=num_classes,
            seed=seed + 1000 * jax.process_index(),
        )
        if len(ds) < per_host_batch:
            raise ValueError(
                f"synthetic dataset length {len(ds)} yields zero batches at "
                f"per-host batch size {per_host_batch}"
            )
        if is_training:
            def epochs() -> Iterator:
                while True:
                    yield from ds.batches(per_host_batch)

            return epochs()
        return ds.batches(per_host_batch)
    if data_format == "tfrecords":
        from distributeddeeplearning_tpu.data import text

        return text.input_fn(
            data_path, is_training, per_host_batch,
            seq_len=seq_len, seed=seed, repeat=is_training,
        )
    raise ValueError(f"unknown data_format {data_format!r}")


def main(
    *,
    model: str = "bert-base",
    data_format: str = "synthetic",
    training_data_path: Optional[str] = None,
    validation_data_path: Optional[str] = None,
    epochs: int = 3,
    batch_size: int = 8,  # per chip
    seq_len: int = 128,
    num_classes: int = 2,
    vocab_size: int = 30522,
    base_lr: float = 3e-5,
    warmup_fraction: float = 0.1,
    weight_decay: float = 0.01,
    grad_clip_norm: float = 1.0,
    accum_steps: int = 1,  # microbatched gradient accumulation (step.py)
    dropout_rate: float = 0.1,
    train_examples: Optional[int] = None,
    steps_per_epoch: Optional[int] = None,
    save_filepath: Optional[str] = None,
    tensorboard_dir: Optional[str] = None,
    resume: bool = True,
    profile_dir: Optional[str] = None,  # jax.profiler trace of steps 10-20
    metrics_path: Optional[str] = None,  # per-epoch JSONL rows (run.log_row)
    seed: int = 42,
    compute_dtype: str = "bfloat16",
    distributed: Optional[bool] = None,
    # parallelism geometry (data absorbs the remainder)
    num_slices: int = 1,  # multi-slice (DCN) data parallelism
    fsdp: int = 1,
    tensor: int = 1,
    seq: int = 1,
    expert: int = 1,
    attention: str = "auto",  # auto|default|flash|ring|ulysses|ulysses-flash
    # ring attention's blocked inner loop: bounds per-tick score memory at
    # O(Sq*block_k) — set for long-context launches (must divide S/seq)
    sp_block_k: Optional[int] = None,
    remat: str = "none",  # none|full|dots — encoder-layer rematerialization
    num_experts: int = 0,  # >0 = MoE FFN in every 2nd layer (models/moe.py)
    # model-size overrides (tiny configs for tests/smoke)
    num_layers: Optional[int] = None,
    hidden_size: Optional[int] = None,
    num_heads: Optional[int] = None,
    intermediate_size: Optional[int] = None,
    max_position_embeddings: Optional[int] = None,
):
    """Fine-tune; returns (state, FitResult)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.ops import make_ring_attention
    from distributeddeeplearning_tpu.parallel import (
        MeshSpec,
        create_mesh,
        initialize,
    )
    from distributeddeeplearning_tpu.parallel.sharding import (
        RULES_DP,
        RULES_EP,
        RULES_FSDP,
        RULES_TP,
        model_logical_axes,
    )
    from distributeddeeplearning_tpu.train.loop import Trainer, TrainerConfig
    from distributeddeeplearning_tpu.train.schedule import (
        warmup_linear_decay_schedule,
    )
    from distributeddeeplearning_tpu.train.state import adamw, create_train_state
    from distributeddeeplearning_tpu.train.step import (
        build_eval_step,
        build_train_step,
        place_state,
    )

    if expert > 1 and num_experts == 0:
        raise ValueError("expert-axis sharding needs --num_experts > 0")
    if num_experts and expert > 1 and num_experts % expert != 0:
        raise ValueError(
            f"num_experts {num_experts} not divisible by expert axis {expert}"
        )
    ctx = initialize(force=distributed)
    mesh = create_mesh(
        MeshSpec(fsdp=fsdp, tensor=tensor, seq=seq, expert=expert),
        num_slices=num_slices,
    )
    world = mesh.devices.size
    batch_shards = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = batch_size * batch_shards
    per_host_batch = global_batch // ctx.process_count
    dtype = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32

    n_train = train_examples or 25_000
    spe = steps_per_epoch or max(n_train // global_batch, 1)
    total_steps = spe * epochs

    if ctx.is_primary:
        logger.info(
            "fine-tuning %s: %d chips (dp=%d fsdp=%d tp=%d sp=%d), "
            "global batch %d, %d steps/epoch, %d epochs",
            model, world, mesh.shape["data"], fsdp, tensor, seq,
            global_batch, spe, epochs,
        )

    model_kwargs = dict(
        num_classes=num_classes,
        vocab_size=vocab_size,
        dropout_rate=dropout_rate,
        dtype=dtype,
        remat=remat,
    )
    if num_experts:
        model_kwargs["num_experts"] = num_experts
    for key, value in (
        ("num_layers", num_layers),
        ("hidden_size", hidden_size),
        ("num_heads", num_heads),
        ("intermediate_size", intermediate_size),
        ("max_position_embeddings", max_position_embeddings),
    ):
        if value is not None:
            model_kwargs[key] = value
    # Attention primitive selection: seq>1 needs a sequence-parallel
    # primitive — "ring" (ppermute rotation, any head count) or "ulysses"
    # (all-to-all head re-sharding, heads % seq == 0); otherwise "flash"
    # injects the Pallas blocked kernel (ops/flash_attention.py), "default"
    # the fused XLA path.
    if attention == "auto":
        attention = "ring" if seq > 1 else "default"
    if seq > 1 and attention not in ("ring", "ulysses", "ulysses-flash"):
        raise ValueError(
            f"seq={seq} requires attention='ring', 'ulysses' or "
            f"'ulysses-flash', got {attention!r}"
        )
    if attention == "ring":
        model_kwargs["attention_fn"] = make_ring_attention(
            mesh, block_k=sp_block_k
        )
    elif attention in ("ulysses", "ulysses-flash"):
        from distributeddeeplearning_tpu.ops import make_ulysses_attention

        # "ulysses-flash" routes the per-device local attention through the
        # Pallas kernel (the Ulysses×flash composition) — the long-context
        # multi-chip encoder path with flash-level local memory.
        model_kwargs["attention_fn"] = make_ulysses_attention(
            mesh, use_flash=attention == "ulysses-flash"
        )
    elif attention == "flash":
        from distributeddeeplearning_tpu.ops.flash_attention import (
            make_flash_attention,
        )

        model_kwargs["attention_fn"] = make_flash_attention(mesh=mesh)
    elif attention != "default":
        raise ValueError(f"unknown attention mode {attention!r}")
    net = get_model(model, **model_kwargs)

    if tensor > 1:
        rules = RULES_TP
    elif fsdp > 1:
        rules = RULES_FSDP
    else:
        rules = RULES_DP
    if num_experts:
        # expert weights [E, ...] shard over the expert axis (no-op at size 1)
        rules = list(rules) + list(RULES_EP)
    if seq_len % max(seq, 1) != 0:
        raise ValueError(f"seq_len {seq_len} not divisible by seq axis {seq}")
    # Init/trace shapes must divide the mesh axes the ring-attention
    # shard_map shards over (batch over data×fsdp, tokens over seq).
    init_shape = (batch_shards, seq_len)
    axes = model_logical_axes(
        net, jax.random.key(seed), np.zeros(init_shape, np.int32), train=False
    )

    schedule = warmup_linear_decay_schedule(
        base_lr, total_steps, warmup_fraction=warmup_fraction
    )
    tx = adamw(
        schedule, weight_decay=weight_decay, grad_clip_norm=grad_clip_norm
    )
    state = create_train_state(
        jax.random.key(seed), net, init_shape, tx, input_dtype=jnp.int32
    )
    train_step = build_train_step(
        mesh, state, schedule=schedule, compute_dtype=dtype,
        rules=rules, logical_axes=axes, rng=jax.random.key(seed + 1),
        accum_steps=accum_steps,
    )
    eval_step = build_eval_step(
        mesh, state, compute_dtype=dtype, rules=rules, logical_axes=axes
    )
    # the placed state is the checkpoint restore template (place_state)
    state = place_state(mesh, state, rules=rules, logical_axes=axes)

    train_iter = _batches(
        data_format, training_data_path, True, per_host_batch,
        seq_len, vocab_size, num_classes, seed, synthetic_length=n_train,
    )
    eval_factory = None
    if validation_data_path or data_format == "synthetic":
        def eval_factory():
            return _batches(
                data_format, validation_data_path, False, per_host_batch,
                seq_len, vocab_size, num_classes, seed,
                synthetic_length=min(n_train, 4 * global_batch),
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
        ),
    )
    return trainer.fit(state, train_iter, eval_factory)


if __name__ == "__main__":
    import logging as _logging

    _logging.basicConfig(level=_logging.INFO)
    from distributeddeeplearning_tpu.workloads._runner import run_from_argv

    run_from_argv(main)
