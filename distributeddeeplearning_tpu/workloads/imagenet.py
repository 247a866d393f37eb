"""ImageNet classification training — the flagship workload.

Capability parity with BOTH reference trainers (they are the same recipe in
two frameworks):
- TF Estimator ResNet-50: ``TensorFlow_imagenet/src/resnet_main.py:37-312``
- PyTorch Horovod ResNet-50: ``PyTorch_imagenet/src/imagenet_pytorch_horovod.py:50-446``

Flags mirror the reference's (fire-parsed there, keyword args here): model
depth, per-chip batch size (64, ``defaults.py:7``), epochs, base LR 0.0125
with Goyal warmup/decay, momentum 0.9, weight decay 5e-5, synthetic/images/
tfrecords input switch, checkpoint/resume, TensorBoard.

TPU-native differences (by design, not omission):
- one process per TPU host drives all local chips through the global-batch
  jitted step; there is no per-GPU rank loop;
- ``steps_per_epoch = NUM_IMAGES // global_batch`` — the reference's
  ``total_batches // hvd.size()`` (``resnet_main.py:246-247``) with the
  division done once;
- eval runs on all chips (the reference restricts eval to rank 0,
  ``resnet_main.py:293-307``, leaving N-1 GPUs idle).
"""

from __future__ import annotations

import logging
from typing import Iterator, Optional

logger = logging.getLogger("ddlt.workloads.imagenet")

NUM_IMAGES = {"train": 1281167, "validation": 50000}  # defaults.py:13-15
NUM_CLASSES = 1001  # defaults.py:11
DEFAULT_BATCH_PER_CHIP = 64  # defaults.py:7
BASE_LR = 0.0125  # imagenet_pytorch_horovod.py:296-302


def _batches(
    data_format: str,
    data_path: Optional[str],
    is_training: bool,
    per_host_batch: int,
    image_size: int,
    num_classes: int,
    seed: Optional[int],
    synthetic_length: Optional[int] = None,
    augment: str = "reference",
    input_pipeline: str = "tf",
    start_batch: int = 0,
) -> Iterator:
    if input_pipeline in ("native", "raw") and data_format != "tfrecords":
        raise ValueError(
            f"input_pipeline={input_pipeline!r} supports "
            f"data_format='tfrecords' only (got {data_format!r})"
        )
    if input_pipeline not in ("tf", "native", "raw"):
        raise ValueError(f"unknown input_pipeline {input_pipeline!r}")
    if data_format == "synthetic":
        import jax

        from distributeddeeplearning_tpu.data.synthetic import SyntheticDataset

        ds = SyntheticDataset(
            length=synthetic_length,
            image_shape=(image_size, image_size, 3),
            num_classes=num_classes,
            # Fold the process index into the seed so hosts contribute
            # distinct slices of the global batch rather than duplicates.
            seed=(seed or 42) + 1000 * jax.process_index(),
        )
        if len(ds) < per_host_batch:
            raise ValueError(
                f"synthetic dataset length {len(ds)} yields zero batches at "
                f"per-host batch size {per_host_batch}"
            )
        if is_training:
            # Regenerate each epoch instead of itertools.cycle(): cycle()
            # caches every yielded batch on the host (~30 GB at the default
            # synthetic epoch length).
            def epochs() -> Iterator:
                while True:
                    yield from ds.batches(per_host_batch)

            return epochs()
        return ds.batches(per_host_batch)
    if data_format == "tfrecords":
        if input_pipeline == "raw":
            # Decode-once uint8 cache (data/raw_cache.py) — the pipeline for
            # decode-bound hosts (streaming JPEG decode cannot keep a chip
            # fed from few cores; not measured on today's code).  Pixels
            # arrive uint8; the train/eval steps normalize ON DEVICE via
            # input_transform (the caller wires uint8_normalizer when
            # input_pipeline == 'raw').
            if augment != "reference":
                raise ValueError(
                    "input_pipeline='raw' caches deterministically-"
                    "preprocessed pixels; augment='reference' only"
                )
            import jax

            from distributeddeeplearning_tpu.data.raw_cache import (
                build_raw_cache,
                cache_path_for,
                raw_cache_input_fn,
            )

            # Per-host cache dir: cache_path_for suffixes the slice when
            # process_count > 1 so hosts on shared storage don't clobber
            # each other's images.u8/manifest.
            cache_dir = cache_path_for(
                data_path, is_training, image_size,
                shard_count=jax.process_count(),
                shard_index=jax.process_index(),
            )
            if jax.process_count() > 1:
                # Each host caches only its own shard-file slice.
                build_raw_cache(
                    data_path, cache_dir, is_training, image_size=image_size,
                    shard_count=jax.process_count(),
                    shard_index=jax.process_index(),
                )
            else:
                build_raw_cache(
                    data_path, cache_dir, is_training, image_size=image_size
                )
            return raw_cache_input_fn(
                cache_dir, is_training, per_host_batch, seed=seed or 0,
                repeat=is_training, start_batch=start_batch,
            )
        if input_pipeline == "native":
            # The framework's own C reader + PIL/numpy path (TF-free);
            # implements the reference recipe only.
            if augment != "reference":
                raise ValueError(
                    "input_pipeline='native' supports augment='reference' only"
                )
            from distributeddeeplearning_tpu.data.native_pipeline import (
                native_input_fn,
            )

            return native_input_fn(
                data_path, is_training, per_host_batch,
                image_size=image_size, seed=seed or 0, repeat=is_training,
            )
        from distributeddeeplearning_tpu.data import tfrecords

        return tfrecords.input_fn(
            data_path, is_training, per_host_batch,
            image_size=image_size, seed=seed, repeat=is_training,
            augment=augment,
        )
    if data_format == "images":
        from distributeddeeplearning_tpu.data import images

        return images.input_fn(
            data_path, is_training, per_host_batch,
            image_size=image_size, seed=seed, repeat=is_training,
            augment=augment,
        )
    raise ValueError(f"unknown data_format {data_format!r}")


def main(
    *,
    model: str = "resnet50",
    data_format: str = "synthetic",
    training_data_path: Optional[str] = None,
    validation_data_path: Optional[str] = None,
    epochs: int = 90,
    batch_size: int = DEFAULT_BATCH_PER_CHIP,  # per chip
    base_lr: float = BASE_LR,
    momentum: float = 0.9,  # imagenet_pytorch_horovod.py:42
    weight_decay: float = 5e-5,  # imagenet_pytorch_horovod.py:43
    warmup_epochs: int = 5,
    label_smoothing: float = 0.0,
    accum_steps: int = 1,  # microbatched gradient accumulation (step.py)
    image_size: int = 224,
    num_classes: int = NUM_CLASSES,
    save_filepath: Optional[str] = None,  # resnet_main.py model_dir analogue
    tensorboard_dir: Optional[str] = None,
    resume: bool = True,
    steps_per_epoch: Optional[int] = None,
    train_images: Optional[int] = None,
    seed: int = 42,
    compute_dtype: str = "bfloat16",
    distributed: Optional[bool] = None,
    augment: str = "reference",  # "inception" = stronger train-time aug
    input_pipeline: str = "tf",  # "native" = C reader+PIL; "raw" = u8 cache
    checkpoint_every_steps: Optional[int] = None,  # mid-epoch save cadence
    profile_dir: Optional[str] = None,  # jax.profiler trace of steps 10-20
    metrics_path: Optional[str] = None,  # per-epoch JSONL rows (run.log_row)
    goodput_path: Optional[str] = None,  # goodput-ledger JSONL (obs/goodput.py)
    aux_logits: bool = False,  # InceptionV3 aux head, loss weighted 0.4
    num_slices: int = 1,  # multi-slice (DCN) data parallelism
    # -- explicit gradient comms (parallel/comms.py; step.py docstrings) --
    comm_overlap: bool = False,  # bucketed reduce-scatter overlap schedule
    bucket_mb: float = 4.0,  # gradient bucket size for comm_overlap
    comm_dtype: Optional[str] = None,  # "bf16" = compressed wire + error feedback
    weight_update_sharding: bool = False,  # ZeRO distributed optimizer (pure DP)
    # -- resilience (train/resilience.py; see TrainerConfig docstrings) --
    skip_nonfinite: bool = False,  # in-step guard: discard non-finite updates
    anomaly_max_consecutive: Optional[int] = None,  # abort after N in a row
    anomaly_rollback: bool = False,  # restore last ckpt instead of aborting
    step_deadline_s: Optional[float] = None,  # watchdog: stacks + exit 70
):
    """Train; returns (state, FitResult)."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh, initialize
    from distributeddeeplearning_tpu.train.loop import Trainer, TrainerConfig
    from distributeddeeplearning_tpu.train.schedule import goyal_lr_schedule
    from distributeddeeplearning_tpu.train.state import (
        create_train_state,
        sgd_momentum,
    )
    from distributeddeeplearning_tpu.train.step import (
        build_eval_step,
        build_train_step,
    )

    ctx = initialize(force=distributed)
    mesh = create_mesh(MeshSpec(), num_slices=num_slices)
    world = mesh.devices.size
    global_batch = batch_size * world
    per_host_batch = global_batch // ctx.process_count

    n_train = train_images or (
        NUM_IMAGES["train"] if data_format != "synthetic" else 50_000
    )
    spe = steps_per_epoch or max(n_train // global_batch, 1)
    dtype = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32

    if ctx.is_primary:
        logger.info(
            "training %s: %d chips, global batch %d, %d steps/epoch, %d epochs",
            model, world, global_batch, spe, epochs,
        )

    model_kwargs = {}
    loss_fn = None
    if aux_logits:
        if "inception" not in model:
            raise ValueError("--aux_logits is an InceptionV3 option")
        from distributeddeeplearning_tpu.models.inception import (
            inception_aux_loss,
        )

        model_kwargs["aux_logits"] = True
        loss_fn = inception_aux_loss
    net = get_model(model, num_classes=num_classes, dtype=dtype, **model_kwargs)
    schedule = goyal_lr_schedule(
        base_lr, world, spe, warmup_epochs=warmup_epochs
    )
    tx = sgd_momentum(schedule, momentum=momentum, weight_decay=weight_decay)
    state = create_train_state(
        jax.random.key(seed), net, (1, image_size, image_size, 3), tx
    )
    step_kwargs = {"loss_fn": loss_fn} if loss_fn is not None else {}
    if input_pipeline == "raw":
        # raw-cache batches are uint8; cast + channel-mean subtraction move
        # on-device (fused by XLA into the first conv's input chain).
        from distributeddeeplearning_tpu.data.raw_cache import uint8_normalizer

        step_kwargs["input_transform"] = uint8_normalizer()
    train_step = build_train_step(
        mesh, state, schedule=schedule, label_smoothing=label_smoothing,
        compute_dtype=dtype, rng=jax.random.key(seed + 1),
        accum_steps=accum_steps, skip_nonfinite=skip_nonfinite,
        comm_overlap=comm_overlap, bucket_mb=bucket_mb,
        comm_dtype=comm_dtype,
        weight_update_sharding=weight_update_sharding,
        **step_kwargs,
    )
    if comm_overlap:
        # flat-shard the optimizer buffers / add the residual slot; the
        # prepared state is ALSO the checkpoint restore template, so
        # resume round-trips the comm layout (residual included)
        state = train_step.prepare_state(state)
    eval_step = build_eval_step(
        mesh, state, compute_dtype=dtype,
        input_transform=step_kwargs.get("input_transform"),
    )

    if input_pipeline == "raw":
        # Step-indexed factory: Trainer.fit resumes by asking for the stream
        # from the restored step, and the raw cache fast-forwards at index-
        # math cost — replay-free exact resume (train/loop.py fit docstring).
        def train_iter(start_step: int):
            return _batches(
                data_format, training_data_path, True, per_host_batch,
                image_size, num_classes, seed, synthetic_length=n_train,
                augment=augment, input_pipeline=input_pipeline,
                start_batch=start_step,
            )
    else:
        train_iter = _batches(
            data_format, training_data_path, True, per_host_batch,
            image_size, num_classes, seed, synthetic_length=n_train,
            augment=augment, input_pipeline=input_pipeline,
        )
    eval_factory = None
    if validation_data_path or data_format == "synthetic":
        def eval_factory():
            return _batches(
                data_format, validation_data_path, False, per_host_batch,
                image_size, num_classes, seed,
                synthetic_length=min(n_train, 4 * global_batch),
                input_pipeline=input_pipeline,
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
            checkpoint_every_steps=checkpoint_every_steps,
            tensorboard_dir=tensorboard_dir,
            resume=resume,
            profile_dir=profile_dir,
            metrics_path=metrics_path,
            goodput_path=goodput_path,
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
