"""Headline benchmark: ResNet-50 synthetic training throughput per chip.

Mirrors the reference's benchmark methodology exactly
(``PyTorch_benchmark/src/pytorch_synthetic_benchmark.py:106-126`` and
tf_cnn_benchmarks submit settings ``tensorflow_benchmark.py:44-56``):
batch 256/chip (the tf_cnn_benchmarks setting), mixed precision (bf16 here,
fp16 there), fixed device-resident synthetic batch, warmup then timed
iterations, img/sec mean ±1.96σ.  The timed unit is the full jitted train
step (fwd+bwd+update — allreduce included when >1 chip).

Beyond the reference's img/sec, the JSON line carries ``mfu`` (sustained
model FLOP/s from XLA's compiled cost model ÷ chip peak bf16 FLOP/s) so the
number is auditable against the hardware ceiling, and ``--trace-dir`` wraps
one timed iteration in ``jax.profiler.trace`` for xprof analysis.

Modes:
  default              one mesh over all visible chips; primary JSON line
  --devices 1,2,4,8    allreduce scaling-efficiency sweep (BASELINE.json's
                       second north-star metric): loop mesh sizes, report
                       efficiency(N) = total_img_sec(N) / (N × img_sec(1)).
                       Re-execs itself onto a virtual N-device CPU platform
                       when fewer real chips are visible (same recipe as
                       ``__graft_entry__.dryrun_multichip``).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
``vs_baseline`` normalizes against 720 img/sec — a representative
tf_cnn_benchmarks ResNet-50 fp16 bs-256 single-V100 figure (the reference
publishes no numbers, BASELINE.md; 10% above/below this is the target band).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

V100_TF_CNN_BENCHMARKS_IMG_SEC = 720.0

#: Revision stamp every default artifact name derives from — bump ONCE per
#: benchmark-schema change instead of editing each emit site's hardcoded
#: ``_rNN`` suffix (the drift that left COMMS at r09 while RESILIENCE sat
#: at r07).  Committed artifacts keep their historical names; NEW runs
#: write ``<KIND>_r{BENCH_REVISION}.json``.
BENCH_REVISION = 21


def artifact_name(kind: str) -> str:
    """Default artifact filename for a benchmark mode, e.g.
    ``artifact_name("QUANT") == "QUANT_r10.json"``."""
    return f"{kind}_r{BENCH_REVISION:02d}.json"


def _is_virtual_pod() -> bool:
    """Recorded in every artifact so CPU numbers can never masquerade as
    hardware — one definition, shared with ``ddlt serve``."""
    from distributeddeeplearning_tpu.utils.virtual_pod import is_virtual_pod

    return is_virtual_pod()


def _ensure_devices(n: int, flag: str):
    """None when ``n`` devices are visible.  Otherwise an exit code: on
    the CPU backend the mode re-runs itself on an ``n``-device virtual
    pod (CPU to CPU — the artifact says ``virtual_pod`` either way) and
    this returns the child's code; on an accelerator with too few chips
    it refuses with one line, because swapping the chips for faked CPUs
    behind the caller's back would put CPU numbers under a chip's name."""
    import jax

    from distributeddeeplearning_tpu.utils.virtual_pod import (
        reexec_with_virtual_pod,
    )

    have = len(jax.devices())
    if have >= n:
        return None
    if jax.default_backend() == "cpu":
        return reexec_with_virtual_pod(max(n, 8))
    print(
        f"[bench] {flag} needs {n} devices and this host has {have} "
        f"({jax.devices()[0].device_kind}); for the platform-independent "
        "half run it on a virtual pod: JAX_PLATFORMS=cpu "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={max(n, 8)}",
        file=sys.stderr,
    )
    return 2


def _build_bert_bench(args, devices=None):
    """BERT fine-tune step benchmark (BASELINE.md's tracked transformer
    config): AdamW, bf16, full-length synthetic token batch, --seq-len."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel import (
        MeshSpec,
        create_mesh,
        shard_batch,
    )
    from distributeddeeplearning_tpu.parallel.sharding import model_logical_axes
    from distributeddeeplearning_tpu.train.schedule import (
        warmup_linear_decay_schedule,
    )
    from distributeddeeplearning_tpu.train.state import adamw, create_train_state
    from distributeddeeplearning_tpu.train.step import build_train_step

    mesh = create_mesh(MeshSpec(), devices=devices)
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16

    model_kwargs = dict(num_classes=2, dropout_rate=0.0, dtype=dtype)
    if args.attention == "flash":
        from distributeddeeplearning_tpu.ops.flash_attention import (
            make_flash_attention,
        )

        model_kwargs["attention_fn"] = make_flash_attention(mesh=mesh)
    if args.remat != "none":
        model_kwargs["remat"] = args.remat
    if args.small:
        # tiny config for CI smoke — full bert-base takes minutes on CPU
        model_kwargs.update(
            num_layers=2, hidden_size=64, num_heads=4, intermediate_size=128,
            vocab_size=1031, max_position_embeddings=args.seq_len,
        )
    model = get_model(args.model, **model_kwargs)
    sched = warmup_linear_decay_schedule(3e-5, 10_000)
    tx = adamw(sched)
    axes = model_logical_axes(
        model, jax.random.key(0),
        np.zeros((global_batch, args.seq_len), np.int32), train=False,
    )
    state = create_train_state(
        jax.random.key(0), model, (global_batch, args.seq_len), tx,
        input_dtype=jnp.int32,
    )
    step = build_train_step(
        mesh, state, schedule=sched, compute_dtype=dtype, logical_axes=axes
    )
    rng = np.random.default_rng(0)
    batch = shard_batch(
        mesh,
        {
            "input": rng.integers(
                0, 1031 if args.small else 30522, (global_batch, args.seq_len)
            ).astype(np.int32),
            "attention_mask": np.ones(
                (global_batch, args.seq_len), np.int32
            ),
            "label": rng.integers(0, 2, (global_batch,)).astype(np.int32),
        },
    )
    init_shape = (global_batch, args.seq_len)
    init_kw = {"input_dtype": jnp.int32}
    return step, state, batch, n_dev, (mesh, model, tx, init_shape, init_kw)


def _build_lm_bench(args, devices=None):
    """Causal-LM step benchmark (decoder path): next-token loss over the
    stacked-transformer model, ``--attention flash`` = the causal Pallas
    kernel (in-kernel triangle + block skip).  The committed seq-2k/8k rows
    (``LM_FLASH_r04.json``) come from this mode."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
        init_params,
        next_token_loss,
        per_token_loss,
    )
    from distributeddeeplearning_tpu.parallel import (
        MeshSpec,
        create_mesh,
        shard_batch,
    )
    from distributeddeeplearning_tpu.train.state import TrainState
    from distributeddeeplearning_tpu.train.step import build_train_step

    mesh = create_mesh(MeshSpec(), devices=devices)
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    dims = dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072,
                vocab_size=32768)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    attention = "flash" if args.attention == "flash" else "dense"
    attention_fn = None
    if attention == "flash" and n_dev > 1:
        # Same GSPMD rule as workloads/transformer.py: a bare pallas_call
        # can't be partitioned, so on a multi-chip mesh the kernel must run
        # per-shard inside shard_map or every chip gathers the global batch
        # (and the sweep would measure the gather, not the step).
        from distributeddeeplearning_tpu.ops import make_flash_attention

        attention_fn = make_flash_attention(mesh=mesh, causal=True)

    params = init_params(
        jax.random.key(0), max_len=args.seq_len, **dims
    )

    def apply_fn(variables, tokens, train=True, mutable=None, rngs=None):
        p = jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            variables["params"],
        )
        if args.loss_chunk:
            # Fused head+CE: "logits" are the per-position losses [b, s-1]
            # (full [b, s, vocab] f32 logits never materialize — the seq-64k
            # memory lever; see models.pipelined_transformer.per_token_loss).
            out = per_token_loss(
                p, tokens, num_heads=dims["num_heads"], attention=attention,
                attention_fn=attention_fn,
                remat=args.remat != "none", loss_chunk=args.loss_chunk,
                unroll=args.scan_unroll,
            )
        else:
            out = forward(
                p, tokens, num_heads=dims["num_heads"], attention=attention,
                attention_fn=attention_fn,
                remat=args.remat != "none", unroll=args.scan_unroll,
            ).astype(jnp.float32)
        if mutable is not None:
            return out, {}
        return out

    tx = optax.adamw(1e-4)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), batch_stats={},
        apply_fn=apply_fn, tx=tx,
    )
    if args.loss_chunk:
        lm_loss_fn = lambda lg, lb, label_smoothing=0.0: lg.mean()  # noqa: E731
    else:
        lm_loss_fn = lambda lg, lb, label_smoothing=0.0: next_token_loss(lg, lb)  # noqa: E731
    step = build_train_step(
        mesh, state, compute_dtype=dtype,
        loss_fn=lm_loss_fn,
        metrics_fn=lambda lg, lb, loss: {"loss": loss.astype(jnp.float32)},
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(
        0, dims["vocab_size"], (global_batch, args.seq_len)
    ).astype(np.int32)
    batch = shard_batch(mesh, {"input": toks, "label": toks})
    init_shape = (global_batch, args.seq_len)
    return step, state, batch, n_dev, (mesh, None, tx, init_shape,
                                       {"input_dtype": jnp.int32})


def _build_bench(args, devices=None, input_transform=None):
    """(step, state, batch, n_dev, parts) for one mesh over ``devices``.

    ``parts`` carries (mesh, model, tx) so callers can mint additional
    TrainStates whose static metadata (apply_fn, tx) matches the jitted
    step — a state built from a NEW model/tx instance would not."""
    if args.model == "lm":
        return _build_lm_bench(args, devices)
    if args.model.startswith("bert"):
        return _build_bert_bench(args, devices)
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.data.synthetic import synthetic_batch
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel import (
        MeshSpec,
        create_mesh,
        shard_batch,
    )
    from distributeddeeplearning_tpu.train.schedule import goyal_lr_schedule
    from distributeddeeplearning_tpu.train.state import (
        create_train_state,
        sgd_momentum,
    )
    from distributeddeeplearning_tpu.train.step import build_train_step

    mesh = create_mesh(MeshSpec(), devices=devices)
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev
    img_shape = (args.image_size, args.image_size, 3)
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16

    model = get_model(args.model, num_classes=1001, dtype=dtype)
    sched = goyal_lr_schedule(0.0125, n_dev, steps_per_epoch=5004)
    tx = sgd_momentum(sched)
    state = create_train_state(
        jax.random.key(0), model, (args.batch_size, *img_shape), tx
    )
    step = build_train_step(
        mesh, state, schedule=sched, compute_dtype=dtype,
        input_transform=input_transform,
    )
    batch = shard_batch(mesh, synthetic_batch(global_batch, img_shape))
    init_shape = (args.batch_size, *img_shape)
    return step, state, batch, n_dev, (mesh, model, tx, init_shape, {})


def _run_single(args) -> int:
    import jax

    from distributeddeeplearning_tpu.train.benchmark import run_benchmark
    from distributeddeeplearning_tpu.utils.hardware import (
        peak_bf16_flops,
        step_flops,
    )

    step, state, batch, n_dev, (mesh, model, tx, init_shape, init_kw) = (
        _build_bench(args)
    )
    global_batch = args.batch_size * n_dev

    # Compile once up front (lowering does not consume the donated state) and
    # read XLA's own FLOP count for the step; the benchmark loop below hits
    # the same jit cache, so this adds no second compilation.
    flops = None
    flops_source = None
    try:
        flops = step_flops(step.lower(state, batch).compile())
    except Exception:
        pass
    if args.model == "lm":
        # XLA's cost model assigns ZERO FLOPs to pallas custom-calls, so the
        # compiled count understates the flash path (and even the dense LM
        # reads low through the scan).  Use the standard analytic MODEL-FLOPs
        # estimate — 6·N·T parameter matmuls (fwd + bwd) plus the CAUSAL
        # attention score/context matmuls 3·2·B·S²·d·L — for BOTH attention
        # modes.  Causal model FLOPs are what the model requires; dense
        # attention also multiplies the masked half, and under this one
        # convention that waste correctly shows up as LOWER MFU rather than
        # inflating it (the r4 advisor flagged the old per-mode convention
        # as incomparable across rows).
        import numpy as _np

        n_params = sum(
            int(_np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(state.params)
        )
        lm_layers, lm_d = (2, 64) if args.small else (12, 768)
        attn_fwd_per_layer = 2 * global_batch * args.seq_len ** 2 * lm_d
        flops = (
            6 * n_params * global_batch * args.seq_len
            + 3 * attn_fwd_per_layer * lm_layers
        )
        flops_source = (
            "analytic causal model flops: 6NT + 3x causal attention matmuls "
            "(2BS^2dL fwd), same convention for dense and flash; XLA cost "
            "model counts pallas custom-calls as 0 FLOPs"
        )

    trace = (
        jax.profiler.trace(args.trace_dir)
        if args.trace_dir
        else contextlib.nullcontext()
    )
    with trace:
        result = run_benchmark(
            step,
            state,
            batch,
            model_name=args.model,
            batch_size_per_chip=args.batch_size,
            num_devices=n_dev,
            num_warmup_batches=args.num_warmup,
            num_iters=args.num_iters,
            num_batches_per_iter=args.num_batches_per_iter,
            log=lambda msg: print(msg, file=sys.stderr),
        )

    mfu = None
    peak = peak_bf16_flops()
    if flops is not None and peak is not None:
        steps_per_sec = result.img_sec_total / global_batch
        mfu = flops * steps_per_sec / (n_dev * peak)

    fit_img_sec = None
    if args.fit:
        # Same step, driven by Trainer.fit over a device-resident iterator:
        # measures the training-loop machinery (metric accumulation, trackers)
        # against the bare harness. The r01 loop lost ~2x here to a per-step
        # host sync; the on-device accumulator must keep it within ~5%.
        import itertools

        from distributeddeeplearning_tpu.train.loop import (
            Trainer,
            TrainerConfig,
        )

        import jax as _jax

        from distributeddeeplearning_tpu.train.state import create_train_state

        # Fresh state with the SAME model/tx objects (identical pytree
        # metadata) driven through the SAME jitted step — no recompile.
        state2 = create_train_state(
            _jax.random.key(1), model, init_shape, tx, **init_kw
        )
        batch2 = batch
        steps = max(args.num_iters * args.num_batches_per_iter, 20)
        trainer = Trainer(
            mesh,
            step,
            config=TrainerConfig(
                epochs=1,
                steps_per_epoch=steps,
                global_batch_size=global_batch,
                log_every=10**9,  # end-of-epoch sync only, like the harness
            ),
        )
        # Warm every jitted path the loop touches (train step reuse, the
        # metric accumulator) with a short fit so the timed epoch measures
        # steady state, not first-call compiles.
        warm_state = create_train_state(
            _jax.random.key(2), model, init_shape, tx, **init_kw
        )
        warm = Trainer(
            mesh,
            step,
            config=TrainerConfig(
                epochs=1, steps_per_epoch=3,
                global_batch_size=global_batch, log_every=10**9,
            ),
        )
        warm.fit(warm_state, itertools.repeat(batch2))
        _, fit_result = trainer.fit(state2, itertools.repeat(batch2))
        fit_img_sec = fit_result.images_per_second / n_dev

    is_bert = args.model.startswith("bert")
    is_lm = args.model == "lm"
    is_vit = args.model.startswith("vit")
    if is_lm:
        metric = (
            f"lm_causal_{args.attention}_seq{args.seq_len}"
            "_train_tok_sec_per_chip"
        )
        value = round(result.img_sec_per_chip_mean * args.seq_len, 1)
        unit = "tok/sec/chip"
    elif is_bert:
        metric = f"{args.model}_synthetic_finetune_ex_sec_per_chip"
        value = round(result.img_sec_per_chip_mean, 1)
        unit = "ex/sec/chip"
    else:
        metric = f"{args.model}_synthetic_train_img_sec_per_chip"
        value = round(result.img_sec_per_chip_mean, 1)
        unit = "img/sec/chip"
    line = {
        "metric": metric,
        "value": value,
        "unit": unit,
        # The V100 yardstick is a ResNet-50 image-throughput figure; for the
        # BERT/LM/ViT modes there is no comparable published baseline, so
        # the field is null rather than a bogus cross-model ratio.
        "vs_baseline": None if (is_bert or is_lm or is_vit) else round(
            result.img_sec_per_chip_mean / V100_TF_CNN_BENCHMARKS_IMG_SEC, 3
        ),
        # A CPU-downgraded run (stale XLA_FLAGS virtual-pod hint, re-exec
        # child) must be distinguishable from a hardware run IN THE
        # ARTIFACT, not just on stderr — same fields _run_scaling records.
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    if mfu is not None:
        line["mfu"] = round(mfu, 4)
    if flops is not None:
        line["step_gflops"] = round(flops / 1e9, 1)
    if flops_source is not None:
        line["flops_source"] = flops_source
    if fit_img_sec is not None:
        line["fit_throughput_per_chip"] = round(fit_img_sec, 1)
        line["fit_vs_harness"] = round(
            fit_img_sec / result.img_sec_per_chip_mean, 3
        )
    print(json.dumps(line))
    return 0


def _run_data(args) -> int:
    """Pipeline-fed benchmark: the same jitted step consuming real batches
    from one of the framework's input pipelines, so the reported img/sec
    includes TFRecord read + JPEG decode (or raw-cache gather) + host→HBM
    transfer.  VERDICT r03 #1: every prior committed number was synthetic;
    this is the proof the chip can actually be fed.

    Pipelines (``--data``):
      tfrecords  tf.data flagship path (``data/tfrecords.py::input_fn``)
      native     TF-free C reader + C JPEG decoder (``data/native_pipeline``)
      raw        decode-once uint8 cache (``data/raw_cache``), normalization
                 on-device via ``input_transform``

    Reports FOUR rates so the feeding question decomposes cleanly:
      host_img_sec       the pipeline alone on this host (no device) — the
                         binding constraint on real TPU-VM hardware, where
                         PCIe DMA overlaps transfers with compute
      staged_img_sec     the jitted step over pre-transferred DISTINCT
                         device batches — the chip-side consume ceiling
      value (fed)        end-to-end: pipeline → prefetch → H2D → step
                         (not measured on today's code: ROADMAP S6)
      synthetic          the same step on one resident batch (the r01-r03
                         headline methodology)
    The pipeline "keeps the chip fed" iff host_img_sec >= staged_img_sec.
    """
    import jax

    from distributeddeeplearning_tpu.data.bench_data import ensure_bench_shards
    from distributeddeeplearning_tpu.train.benchmark import (
        run_benchmark,
        run_data_benchmark,
    )
    from distributeddeeplearning_tpu.utils.prefetch import prefetch_to_device

    data_dir = ensure_bench_shards(
        args.data_dir, num_images=args.data_images, num_shards=8
    )

    input_transform = None
    if args.data == "raw":
        from distributeddeeplearning_tpu.data.raw_cache import uint8_normalizer

        input_transform = uint8_normalizer()
    step, state, batch, n_dev, (mesh, model, tx, init_shape, init_kw) = (
        _build_bench(args, input_transform=input_transform)
    )
    global_batch = args.batch_size * n_dev
    per_host_batch = global_batch // jax.process_count()

    # Synthetic reference on the SAME step/model/batch — the ceiling the
    # pipeline is judged against.
    synth = run_benchmark(
        step,
        state,
        batch,
        model_name=args.model,
        batch_size_per_chip=args.batch_size,
        num_devices=n_dev,
        num_warmup_batches=args.num_warmup,
        num_iters=max(args.num_iters // 2, 2),
        num_batches_per_iter=args.num_batches_per_iter,
        log=lambda msg: print(f"[synthetic] {msg}", file=sys.stderr),
    )

    if args.data == "tfrecords":
        from distributeddeeplearning_tpu.data.tfrecords import input_fn

        host_batches = input_fn(
            data_dir, True, per_host_batch, seed=0,
            shuffle_buffer=min(10000, args.data_images),
        )
    elif args.data == "native":
        from distributeddeeplearning_tpu.data.native_pipeline import (
            native_input_fn,
        )

        host_batches = native_input_fn(
            data_dir, True, per_host_batch, seed=0,
            shuffle_buffer=min(10000, args.data_images),
        )
    else:  # raw
        from distributeddeeplearning_tpu.data.raw_cache import (
            build_raw_cache,
            cache_path_for,
            raw_cache_input_fn,
        )

        cache_dir = cache_path_for(data_dir, True, args.image_size)
        build_raw_cache(data_dir, cache_dir, True, image_size=args.image_size)
        host_batches = raw_cache_input_fn(cache_dir, True, per_host_batch)

    import time as _time

    from distributeddeeplearning_tpu.parallel import shard_batch as _shard
    from distributeddeeplearning_tpu.train.state import create_train_state

    # --- host production rate: the pipeline alone, no device involved ---
    host_iter = iter(host_batches)
    for _ in range(2):  # spin up decode threads / page cache
        next(host_iter)
    n_host = 12
    t0 = _time.perf_counter()
    host_images = sum(len(next(host_iter)["label"]) for _ in range(n_host))
    host_rate = host_images / (_time.perf_counter() - t0)
    print(f"[{args.data}] host pipeline: {host_rate:.1f} img/s", file=sys.stderr)

    # --- staged consume rate: pre-transferred distinct batches, full-rate
    # steps (proves varying-input execution, minus the H2D transfers) ---
    staged = [_shard(mesh, next(host_iter)) for _ in range(8)]
    for b in staged:
        jax.block_until_ready(b)
    state2 = create_train_state(
        jax.random.key(1), model, init_shape, tx, **init_kw
    )
    metrics = None
    for i in range(4):
        state2, metrics = step(state2, staged[i % 8])
    float(metrics["loss"])
    n_staged = 20
    t0 = _time.perf_counter()
    for i in range(n_staged):
        state2, metrics = step(state2, staged[i % 8])
    float(metrics["loss"])
    staged_rate = n_staged * global_batch / (_time.perf_counter() - t0) / n_dev
    print(f"[{args.data}] staged steps: {staged_rate:.1f} img/s/chip", file=sys.stderr)

    # --- end-to-end fed rate ---
    state3 = create_train_state(
        jax.random.key(2), model, init_shape, tx, **init_kw
    )
    staged_iter = prefetch_to_device(host_iter, mesh, size=args.prefetch)
    try:
        fed = run_data_benchmark(
            step,
            state3,
            staged_iter,
            model_name=args.model,
            batch_size_per_chip=args.batch_size,
            num_devices=n_dev,
            num_warmup_batches=args.num_warmup,
            num_iters=args.num_iters,
            num_batches_per_iter=args.num_batches_per_iter,
            log=lambda msg: print(f"[{args.data}] {msg}", file=sys.stderr),
        )
    finally:
        # reap the worker: it would otherwise sit blocked on a full queue
        # holding `prefetch` device-resident batches for the rest of the
        # process
        staged_iter.close()

    print(
        json.dumps(
            {
                "metric": f"{args.model}_{args.data}_train_img_sec_per_chip",
                "value": round(fed.img_sec_per_chip_mean, 1),
                "unit": "img/sec/chip",
                "vs_baseline": round(
                    fed.img_sec_per_chip_mean / V100_TF_CNN_BENCHMARKS_IMG_SEC, 3
                ),
                "pipeline": args.data,
                "host_img_sec": round(host_rate, 1),
                "staged_img_sec_per_chip": round(staged_rate, 1),
                "synthetic_img_sec_per_chip": round(
                    synth.img_sec_per_chip_mean, 1
                ),
                "fed_vs_synthetic": round(
                    fed.img_sec_per_chip_mean / synth.img_sec_per_chip_mean, 3
                ),
                "host_vs_staged": round(host_rate / max(staged_rate, 1e-9), 3),
                "ci95": round(fed.img_sec_per_chip_ci95, 1),
                "num_images": args.data_images,
                "prefetch": args.prefetch,
                "host_cores": __import__("os").cpu_count(),
            }
        )
    )
    return 0


def _run_roofline(args) -> int:
    """Trace K steady-state steps and emit the roofline verdict as JSON.

    Regenerates the README's "where the roofline actually is" analysis from
    a fresh trace (VERDICT r03 #3): HBM GB/step, per-category sustained
    GB/s / TFLOP/s, bandwidth-bound time fraction, and the implied ceiling
    img/s next to the measured rate.  Artifact: ``ROOFLINE_r{N}.json``.
    """
    import tempfile

    import jax

    from distributeddeeplearning_tpu.utils.hardware import peak_bf16_flops
    from distributeddeeplearning_tpu.utils.roofline import analyze_trace

    step, state, batch, n_dev, _ = _build_bench(args)
    global_batch = args.batch_size * n_dev

    metrics = None
    for _ in range(4):  # >=3: layout-donation double compile + steady state
        state, metrics = step(state, batch)
    float(metrics["loss"])

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="ddlt-roofline-")
    k = args.roofline_steps
    with jax.profiler.trace(trace_dir):
        for _ in range(k):
            state, metrics = step(state, batch)
        float(metrics["loss"])

    peak = peak_bf16_flops()
    result = analyze_trace(
        trace_dir,
        steps=k,
        global_batch=global_batch,
        peak_tflops=(peak / 1e12) if peak else 394.0,
    )
    line = {
        "metric": f"{args.model}_roofline_ceiling_img_sec",
        "value": result.get("implied_ceiling_img_sec"),
        "unit": "img/sec",
        "vs_baseline": result["pct_of_bandwidth_ceiling"],
        "trace_dir": trace_dir,
    }
    line.update(result)
    print(json.dumps(line))
    return 0


def _serve_warmup(
    engine, max_seq, requests, *, vocab_size, spec_decoder=None
) -> None:
    """Compile EVERY prefill shape the request set will hit plus the
    decode step, so the timed run measures serving, not XLA.

    Dense: one prompt per distinct power-of-two prompt bucket.  Paged:
    one prompt per possible chunk shape (full chunk + the power-of-two
    final-chunk buckets), each with DISTINCT token values so warmup
    prompts cannot prefix-hit each other and skip a shape.  Budget THREE
    tokens: the first comes from prefill at admission (a 1-token budget
    never decodes at all), and the donated-cache decode needs TWO steps
    to reach steady state — the first call compiles, the second
    recompiles with the output layouts fed back as input layouts (the
    layout-donation double compile, same as the train step).

    After warmup the engine's run counters (and, for paged, the prefix
    table the warmup prompts seeded) are reset, so the benchmarked phase
    reports ``prefill_compiles == 0`` and an honest prefix-hit rate.
    """
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributeddeeplearning_tpu.serve.engine import prompt_bucket

    if getattr(engine, "chunked_prefill", False):
        C = engine.prefill_chunk
        shapes, b = {C}, 8
        while b < C:
            shapes.add(b)
            b *= 2
        warm = [
            Request(uid=f"warmup{i}", prompt=[(i % (vocab_size - 1)) + 1] * s)
            for i, s in enumerate(sorted(shapes))
            if s < engine.max_seq
        ]
    else:
        buckets = {}
        for r in requests:
            buckets.setdefault(prompt_bucket(len(r.prompt), max_seq), r.prompt)
        warm = [
            Request(uid=f"warmup{i}", prompt=p)
            for i, p in enumerate(buckets.values())
        ]
    # spec runs need a budget that outlasts one full acceptance (a K=4
    # spec step can commit 5 tokens), or warmup would never reach the
    # donated-cache second step that finishes the layout-feedback compile
    budget = 3 if spec_decoder is None else 2 * spec_decoder.draft_tokens + 2
    _, warm_report = ContinuousBatchingScheduler(
        engine, max_new_tokens=budget, spec_decoder=spec_decoder
    ).run(warm)
    assert warm_report.decode_steps >= 2, "warmup never reached decode"
    if spec_decoder is not None:
        # the rollback program only dispatches on a rejected tail, which
        # an all-accepting warmup may never produce — compile it (twice:
        # the donated-layout double compile) on a no-op keep vector
        import numpy as _np

        noop = _np.full(engine.batch_slots, spec_decoder.draft_tokens + 1,
                        _np.int32)
        zeros = _np.zeros(engine.batch_slots, _np.int32)
        spec_decoder.rollback(zeros, noop)
        spec_decoder.rollback(zeros, noop)
    if hasattr(engine, "reset_stats"):
        engine.reset_stats()
    if hasattr(engine, "clear_prefix_cache"):
        engine.clear_prefix_cache()
    engine.prefill_compiles = 0


def _serve_line(report, engine, args, *, max_prompt, mesh=None):
    """One engine run -> the SERVE artifact dict (ServeReport.to_dict(),
    the README-documented keys, plus headline + ms conveniences)."""
    import jax

    admitted = report.prompt_tokens + report.generated_tokens
    return {
        **report.to_dict(),
        "ttft_ms": {
            "p50": round(report.ttft_s["p50"] * 1e3, 2),
            "p99": round(report.ttft_s["p99"] * 1e3, 2),
        },
        "decode_step_ms": {
            "p50": round(report.decode_step_s["p50"] * 1e3, 3),
            "p99": round(report.decode_step_s["p99"] * 1e3, 3),
        },
        "max_new_tokens": args.max_new_tokens,
        "max_prompt_len": max_prompt,
        "kv_cache_mb": round(engine.kv_bytes() / 1e6, 3),
        "hbm_bytes_per_admitted_token": (
            round(report.kv_bytes_peak / admitted, 2) if admitted else None
        ),
        "mesh_devices": (
            int(mesh.devices.size) if mesh is not None else 1
        ),
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }


def _run_serve(args) -> int:
    """Serving benchmark: the KV-cached engine under continuous batching.

    Builds the causal LM at the same dims as ``--model lm`` (``--small``
    shrinks it), admits ``--serve-requests`` synthetic prompts (more than
    ``--batch-slots``, so slot release/reuse is exercised) and emits ONE
    JSON line — the ``SERVE_*.json`` artifact: generated tokens/s, TTFT
    p50/p99, queue wait, per-decode-step latency, mean slot occupancy,
    platform + virtual_pod provenance.

    ``--kv-layout`` selects the cache layout: ``dense`` (per-slot
    ``max_seq`` reservation), ``paged`` (page pool + block tables +
    chunked prefill), or ``both`` — the paged-vs-dense comparison
    (``SERVE_PAGED_*.json``): identical mixed-length greedy traffic
    through both layouts (generated tokens asserted bit-identical), HBM
    bytes per admitted token for each, plus a shared-prefix workload for
    the prefix-cache hit rate.  In ``both`` mode ``max_seq`` is
    provisioned with headroom (4x the longest request) the way a server
    sizes its context window — the dense layout must reserve it per slot,
    the paged layout commits pages only for actual tokens, which is the
    entire comparison.
    """
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedInferenceEngine,
        data_parallel_engine,
        synthetic_requests,
    )

    dims = dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072,
                vocab_size=32768)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    compare = args.kv_layout == "both"
    max_prompt = max(8, args.seq_len)
    if compare:
        # provisioning headroom: a server sizes max_seq for the longest
        # ADMISSIBLE request, not the longest observed — dense pays it
        # per slot, paged pays per actual token
        max_seq = 4 * (max_prompt + args.max_new_tokens)
    else:
        max_seq = max_prompt + args.max_new_tokens
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)

    def build(layout):
        if layout == "paged":
            return PagedInferenceEngine(
                params,
                num_heads=dims["num_heads"],
                batch_slots=args.batch_slots,
                max_seq=max_seq,
                page_size=args.page_size,
                num_pages=args.kv_pages,
                prefill_chunk=args.prefill_chunk,
                temperature=args.serve_temperature,
                rng=jax.random.key(1),
            ), None
        return data_parallel_engine(
            params,
            num_heads=dims["num_heads"],
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            prefill_attention=(
                "flash" if args.attention == "flash" else "dense"
            ),
            temperature=args.serve_temperature,
            rng=jax.random.key(1),
        )

    def run_one(engine, requests):
        # smoke mode (--steps-cap) skips warmup: the point is a fast
        # scheduler/allocator exercise, not clean timings
        if args.steps_cap is None:
            _serve_warmup(
                engine, max_seq, requests, vocab_size=dims["vocab_size"]
            )
        results, report = ContinuousBatchingScheduler(
            engine,
            max_new_tokens=args.max_new_tokens,
            step_cap=args.steps_cap,
        ).run(list(requests))
        if args.steps_cap is None:
            assert report.prefill_compiles == 0, (
                f"warmup missed {report.prefill_compiles} prefill "
                "shape(s) — the timed phase hit mid-run compiles"
            )
        return results, report

    if not compare:
        engine, mesh = build(args.kv_layout)
        requests = synthetic_requests(
            args.serve_requests, vocab_size=dims["vocab_size"],
            max_prompt=max_prompt, min_prompt=max_prompt // 2,
            rng=np.random.default_rng(0),
        )
        results, report = run_one(engine, requests)
        line = {
            "metric": f"lm_serve_{args.attention}_tok_sec",
            "value": report.tokens_per_sec,
            "unit": "tok/sec",
            "vs_baseline": None,
            **_serve_line(report, engine, args,
                          max_prompt=max_prompt, mesh=mesh),
        }
    else:
        # ---- paged vs dense: identical mixed-length greedy traffic ----
        mixed = synthetic_requests(
            args.serve_requests, vocab_size=dims["vocab_size"],
            max_prompt=max_prompt, min_prompt=max(2, max_prompt // 8),
            rng=np.random.default_rng(0),
        )
        dense_engine, mesh = build("dense")
        dense_res, dense_rep = run_one(dense_engine, mixed)
        paged_engine, _ = build("paged")
        paged_res, paged_rep = run_one(paged_engine, mixed)
        # the gate compares dense-math prefill on both sides: the Pallas
        # flash kernel's online-softmax reduction order differs in ulps
        # from the paged chunk program's dense math, so a near-tie argmax
        # could flip a token without either layout being wrong
        bit_exact_gate = (
            args.serve_temperature <= 0
            and args.steps_cap is None
            and args.attention != "flash"
        )
        if bit_exact_gate:
            d = {r.uid: r.tokens for r in dense_res}
            p = {r.uid: r.tokens for r in paged_res}
            assert d == p, (
                "paged decode diverged from dense on identical greedy "
                "traffic — the layouts are no longer bit-exact"
            )
        # ---- shared-prefix workload: the prefix-cache column ----
        shared = synthetic_requests(
            args.serve_requests, vocab_size=dims["vocab_size"],
            max_prompt=max(2, max_prompt // 2),
            min_prompt=2,
            shared_prefix_len=max_prompt // 2,
            rng=np.random.default_rng(1),
        )
        _, shared_rep = run_one(paged_engine, shared)
        d_line = _serve_line(dense_rep, dense_engine, args,
                             max_prompt=max_prompt, mesh=mesh)
        p_line = _serve_line(paged_rep, paged_engine, args,
                             max_prompt=max_prompt)
        ratio = (
            round(
                d_line["hbm_bytes_per_admitted_token"]
                / p_line["hbm_bytes_per_admitted_token"], 2,
            )
            if p_line["hbm_bytes_per_admitted_token"]
            else None
        )
        line = {
            "metric": "lm_serve_paged_vs_dense_hbm_ratio",
            # admitted-tokens-per-HBM-byte improvement of paged over dense
            "value": ratio,
            "unit": "x",
            "vs_baseline": None,
            "bit_exact_vs_dense": bit_exact_gate,
            "max_seq_provisioned": max_seq,
            "page_size": args.page_size,
            "prefill_chunk": args.prefill_chunk,
            "tokens_per_sec": {
                "dense": dense_rep.tokens_per_sec,
                "paged": paged_rep.tokens_per_sec,
            },
            "decode_tokens_per_sec": {
                "dense": dense_rep.decode_tokens_per_sec,
                "paged": paged_rep.decode_tokens_per_sec,
            },
            "prefix_hit_rate_shared_workload": shared_rep.prefix_hit_rate,
            "dense": d_line,
            "paged": p_line,
            "paged_shared_prefix": {
                "prefix_hit_rate": shared_rep.prefix_hit_rate,
                "tokens_per_sec": shared_rep.tokens_per_sec,
                "ttft_s": shared_rep.ttft_s,
            },
            "platform": jax.default_backend(),
            "virtual_pod": _is_virtual_pod(),
        }
    print(json.dumps(line))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    return 0


def _run_quant(args) -> int:
    """Quantized-serving benchmark: int8 KV (± int8 weights) vs f32 paged.

    Five paged engines over the SAME model and identical greedy traffic:

    - ``f32`` — the baseline, flash-decode kernel (``--decode-kernel
      auto``; off-TPU the fused-XLA twin, bitwise == gather for f32);
    - ``kv_int8`` — int8 KV pages through the flash-decode kernel:
      per-(position, head) scales applied in-tile (TPU) / folded into
      the score vectors (XLA twin), f32 history never materialized —
      ROADMAP Open item 2(a);
    - ``kv_w_int8`` — int8 KV (flash) plus int8 matmul weights;
    - ``f32_gather`` / ``kv_int8_gather`` — the legacy gather path, kept
      in the artifact as the reference exhibits: ``f32_gather`` proves
      flash f32 is bit-identical token-for-token, ``kv_int8_gather``
      shows the QUANT_r10 regression the kernel kills.

    The artifact (``QUANT_r{NN}.json``) answers the deployment question:
    per-config KV HBM bytes INCLUDING scale overhead, admitted
    tokens/HBM-byte vs the f32 baseline, decode step time + decode-phase
    tokens/sec per config, and greedy agreement + per-position logit MAE
    from a teacher-forced probe over the whole workload (both engines
    decode the f32 engine's greedy stream, so position i compares
    like-for-like states — in the raw batching streams one near-tie flip
    rewrites a sequence's tail, which measures cascade luck, not
    fidelity; the raw stream match is still reported).  Full (non
    ``--steps-cap``) runs gate: per-position agreement >= 99%, int8
    kv_bytes <= 55% of f32, ``prefill_compiles == 0`` in the benchmarked
    phase, AND the both-axes win — ``kv_int8 decode_tokens_per_sec >=
    f32`` (the speed regression Open item 2 existed to kill; rc 1 on
    violation).  The f32 flash-vs-gather token streams are asserted
    bit-identical in every mode, smoke included.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.quant.calibrate import quantize_params
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedInferenceEngine,
        synthetic_requests,
    )

    dims = dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072,
                vocab_size=32768)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    max_prompt = max(8, args.seq_len)
    max_seq = max_prompt + args.max_new_tokens
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)
    # Sharpen the synthetic LM toward a TRAINED model's margin profile:
    # GPT-2-style weight tying with a boosted embedding, so the token-
    # identity component dominates the residual stream and top-2 logit
    # gaps sit orders of magnitude above the int8 logit error — the
    # regime every deployed LM decodes in.  A raw random-init head
    # yields near-TIED logits (top-2 gaps ~1e-2 at vocab 32k, iid
    # Gaussian order statistics) where greedy agreement measures argmax
    # tie-breaking against noise, not quantization fidelity.  Logit MAE
    # is reported unconditionally either way.
    params["embed"] = params["embed"] * 4.0
    params["head"] = params["embed"].T
    qparams = quantize_params(params)

    def build(cache_dtype=None, ps=params, decode_kernel="auto"):
        return PagedInferenceEngine(
            ps,
            num_heads=dims["num_heads"],
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            page_size=args.page_size,
            num_pages=args.kv_pages,
            prefill_chunk=args.prefill_chunk,
            temperature=0.0,  # greedy: the agreement gate needs determinism
            rng=jax.random.key(1),
            cache_dtype=cache_dtype,
            decode_kernel=decode_kernel,
        )

    engines = {
        "f32": build(),
        "kv_int8": build(jnp.int8),
        "kv_w_int8": build(jnp.int8, qparams),
        # legacy-path exhibits (see docstring): the bit-identity
        # cross-check and the killed regression, in the same artifact
        "f32_gather": build(decode_kernel="gather"),
        "kv_int8_gather": build(jnp.int8, decode_kernel="gather"),
    }
    requests = synthetic_requests(
        args.serve_requests, vocab_size=dims["vocab_size"],
        max_prompt=max_prompt, min_prompt=max(2, max_prompt // 8),
        rng=np.random.default_rng(0),
    )

    def run_one(engine):
        if args.steps_cap is None:
            _serve_warmup(
                engine, max_seq, requests, vocab_size=dims["vocab_size"]
            )
        results, report = ContinuousBatchingScheduler(
            engine,
            max_new_tokens=args.max_new_tokens,
            step_cap=args.steps_cap,
        ).run(list(requests))
        if args.steps_cap is None:
            assert report.prefill_compiles == 0, (
                f"warmup missed {report.prefill_compiles} prefill shape(s)"
            )
        return {r.uid: r.tokens for r in results}, report

    tokens = {}
    reports = {}
    for name, engine in engines.items():
        tokens[name], reports[name] = run_one(engine)

    # f32 flash vs gather: bit-identical greedy streams, asserted in
    # EVERY mode (smoke included) — off-TPU the flash twin is op-for-op
    # the gather program, and this is the executed proof.  On TPU the
    # flash path is the Pallas online-softmax kernel, whose block
    # accumulation legitimately perturbs f32 logits in the last ulp —
    # there the comparison is recorded, not asserted (near-tied
    # random-init logits can flip argmax on ulp noise; the kernel's
    # numeric pin lives in tests/test_flash_decode.py's tolerance +
    # argmax tests).
    flash_f32_bit_identical = tokens["f32"] == tokens["f32_gather"]
    if jax.default_backend() != "tpu":
        assert flash_f32_bit_identical, (
            "f32 flash-decode tokens diverged from the gather reference"
        )

    def agreement(ref, other):
        tot = match = 0
        for uid, seq in ref.items():
            for a, b in zip(seq, other.get(uid, [])):
                tot += 1
                match += int(a == b)
        return round(match / tot, 4) if tot else None

    agree_stream = {
        name: agreement(tokens["f32"], tokens[name])
        for name in ("kv_int8", "kv_w_int8")
    }

    # ---- teacher-forced fidelity probe over the WHOLE workload: both
    # engines decode the f32 engine's greedy stream, so position i
    # compares like-for-like states.  This is the per-position agreement
    # the gate runs on — in the raw continuous-batching streams a single
    # near-tie argmax flip (random-init logits are nearly flat) rewrites
    # every later token of that sequence, so stream agreement measures
    # cascade luck, not quantization fidelity; it is still reported. ----
    # every prompt is probeable: the engine admits any prompt shorter
    # than max_seq, and the per-prompt step budget below keeps the
    # teacher-forced walk inside the position table
    probe_prompts = [r.prompt for r in requests]
    for engine in engines.values():
        engine.capture_logits = True

    def prompt_steps(prompt) -> int:
        return min(args.max_new_tokens - 1, max_seq - len(prompt) - 1)

    def greedy_stream(engine, prompt, teacher=None):
        """Prefill + decode on slot 0, capturing per-position logits.
        ``teacher`` (a prior stream) supplies the tokens to decode —
        the teacher-forced probe; None means self-feed (argmax of the
        engine's own last logits — used once, for the f32 reference)."""
        steps = prompt_steps(prompt)
        logits = []
        engine.prefill(0, prompt, max_new_tokens=steps + 1)
        logits.append(engine.last_prefill_logits)
        tok_buf = np.zeros(engine.batch_slots, np.int32)
        pos_buf = np.zeros(engine.batch_slots, np.int32)
        pos = len(prompt)
        for i in range(steps):
            src = logits if teacher is None else teacher
            tok_buf[0] = int(np.argmax(src[i]))
            pos_buf[0] = pos
            engine.decode(tok_buf, pos_buf)
            logits.append(engine.last_logits[0])
            pos += 1
        engine.release(0)
        return logits

    ref_streams = {
        tuple(p): greedy_stream(engines["f32"], p) for p in probe_prompts
    }

    def probe(eng_q):
        maes, agree, n = [], 0, 0
        for prompt in probe_prompts:
            ref = ref_streams[tuple(prompt)]
            q_logits = greedy_stream(eng_q, prompt, teacher=ref)
            for lr, lq in zip(ref, q_logits):
                maes.append(float(np.abs(lr - lq).mean()))
                agree += int(np.argmax(lr) == np.argmax(lq))
                n += 1
        return {
            "logit_mae": round(float(np.mean(maes)), 6),
            "logit_mae_max": round(float(np.max(maes)), 6),
            "greedy_agreement": round(agree / n, 4),
            "positions": n,
        }

    fidelity = {
        name: probe(engines[name]) for name in ("kv_int8", "kv_w_int8")
    }

    lines = {
        name: _serve_line(reports[name], engines[name], args,
                          max_prompt=max_prompt)
        for name in engines
    }
    kv_ratio = round(
        reports["kv_int8"].kv_bytes / reports["f32"].kv_bytes, 4
    )
    # Per-byte throughput is O(1e-6) at full geometry — fixed decimal
    # rounding would collapse it to one significant digit (and corrupt
    # the derived ratio), so ratios come from the raw values and the
    # reported figures keep 4 significant digits.
    _tok_per_byte_raw = {
        name: (
            (rep.prompt_tokens + rep.generated_tokens) / rep.kv_bytes_peak
            if rep.kv_bytes_peak
            else None
        )
        for name, rep in reports.items()
    }
    tok_per_byte = {
        name: (float(f"{v:.4g}") if v else None)
        for name, v in _tok_per_byte_raw.items()
    }
    tok_per_byte_vs_f32 = {
        name: (
            round(_tok_per_byte_raw[name] / _tok_per_byte_raw["f32"], 2)
            if _tok_per_byte_raw[name] and _tok_per_byte_raw["f32"]
            else None
        )
        for name in ("kv_int8", "kv_w_int8")
    }

    if args.steps_cap is None:
        assert kv_ratio <= 0.55, (
            f"int8 KV bytes (incl. scales) are {kv_ratio:.2%} of f32 — "
            "the quantized layout lost its HBM win"
        )
        assert fidelity["kv_int8"]["greedy_agreement"] >= 0.99, (
            f"int8-KV greedy tokens agree with f32 on only "
            f"{fidelity['kv_int8']['greedy_agreement']:.2%} of "
            "teacher-forced positions (< 99%)"
        )
        # THE both-axes gate (ROADMAP Open item 2): int8 already won on
        # bytes above — with the flash-decode kernel it must also win
        # (or tie) on decode-phase throughput, or the capacity win is
        # still paying a latency tax
        f32_tps = reports["f32"].decode_tokens_per_sec
        int8_tps = reports["kv_int8"].decode_tokens_per_sec
        assert int8_tps >= f32_tps, (
            f"kv_int8 decode tokens/sec {int8_tps} < f32 baseline "
            f"{f32_tps} — the int8 speed regression is back"
        )

    line = {
        "metric": "lm_serve_int8_kv_bytes_vs_f32_ratio",
        # KV pool bytes (values + scales) as a fraction of the f32 pool
        "value": kv_ratio,
        "unit": "x",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "model": "synthetic LM, tied embedding head (4x embed gain — "
                 "trained-model margin profile)",
        "max_seq": max_seq,
        "page_size": args.page_size,
        "prefill_chunk": args.prefill_chunk,
        "scale_layout": "f32 per (position, head) over head_dim",
        "decode_kernel": {
            name: rep.decode_kernel for name, rep in reports.items()
        },
        # f32 flash vs gather greedy streams compared token-for-token
        # (asserted, but recorded so the artifact carries the proof)
        "flash_f32_bit_identical_to_gather": flash_f32_bit_identical,
        "admitted_tokens_per_hbm_byte": tok_per_byte,
        "admitted_tokens_per_hbm_byte_vs_f32": tok_per_byte_vs_f32,
        # per-position (teacher-forced, cascade-free) — the gated number
        "greedy_agreement_vs_f32": {
            name: fidelity[name]["greedy_agreement"]
            for name in ("kv_int8", "kv_w_int8")
        },
        # raw continuous-batching stream match: one near-tie flip
        # rewrites a sequence's whole tail, so this trails the
        # per-position number on near-flat random-init logits
        "stream_greedy_agreement_vs_f32": agree_stream,
        "fidelity_probe": fidelity,
        "decode_step_ms": {
            name: round(rep.decode_step_s["p50"] * 1e3, 3)
            for name, rep in reports.items()
        },
        "tokens_per_sec": {
            name: rep.tokens_per_sec for name, rep in reports.items()
        },
        # decode-phase-only throughput (prefill/compile wall excluded) —
        # the number decode-path changes are actually judged on; the
        # whole-wall tokens_per_sec above skews with prompt mix
        "decode_tokens_per_sec": {
            name: rep.decode_tokens_per_sec
            for name, rep in reports.items()
        },
        # the both-axes verdict (gated on full runs): int8 wins bytes
        # (kv_ratio above) AND decode-phase throughput
        "kv_int8_decode_speed_win": (
            reports["kv_int8"].decode_tokens_per_sec
            >= reports["f32"].decode_tokens_per_sec
        ),
        "configs": lines,
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps(line))
    report_path = args.report or artifact_name("QUANT")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    return 0


def _run_tp(args) -> int:
    """Tensor-parallel serving benchmark: TP=1 vs TP=N at FIXED model size
    (the ``TP_r{NN}.json`` artifact, on a virtual pod off-TPU).

    Two engine layouts at both TP degrees over identical greedy traffic —
    dense f32 and paged int8, built by ``serve.engine.tensor_parallel_
    engine`` so every placement (params, KV pages, int8 scale leaves, jit
    io) resolves through the partition-rule table in
    ``parallel/sharding.py`` (the artifact records the table's provenance
    stamp).  Three gates, enforced on full-geometry runs (rc 1):

    - **bit-identical tokens** — the TP=N greedy stream must equal TP=1
      token-for-token on every config.  Megatron sharding only reorders
      the reduction through its per-block all-reduce; with the margin-
      profiled synthetic model (tied 4x-gain embedding head — trained-
      model top-2 logit gaps) the argmax is invariant, so the gate is
      exact stream equality, not an agreement rate.
    - **per-chip param HBM** — ledger-attributed (``obs/ledger``'s
      sharding-metadata walk, never touching shard data): the max-over-
      chips param bytes at TP=N must be <= 0.55x the TP=1 figure (~1/N
      plus the replicated ln/pos slack the table deliberately leaves).
    - **decode latency** — the per-chip ROOFLINE time of the compiled
      decode program (post-partitioning ``cost_analysis`` flops/bytes
      over the ``obs/attrib.reference_peaks`` ceilings — deterministic
      on the virtual pod, where wall-clock is host-core-contention
      noise) must be STRICTLY below TP=1 for every config.  Measured
      decode wall is recorded alongside, labeled informational.

    The TP decode HLO's collective signature is recorded through
    ``parallel/comms.collective_stats(mesh=...)``, which classifies the
    per-block tensor all-reduces under ``tp-all-reduce`` — pinned >= 1
    here (a collective-free TP "win" would mean the weights silently
    replicated behind the table's back) and kept out of the gradient
    all-reduce count the comm-path lint audits.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    rc = _ensure_devices(args.tp, "--tp")  # TP needs real shards
    if rc is not None:
        return rc

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.obs import ledger as _ledger
    from distributeddeeplearning_tpu.obs.attrib import reference_peaks
    from distributeddeeplearning_tpu.parallel import comms
    from distributeddeeplearning_tpu.parallel import sharding as _layout
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.serve.engine import (
        tensor_parallel_engine,
    )
    from distributeddeeplearning_tpu.utils.roofline import program_roofline

    tp = args.tp
    dims = dict(num_layers=4, d_model=512, num_heads=8, d_ff=2048,
                vocab_size=8192)
    if args.small:
        # smoke geometry: the replicated ln/pos leaves dominate a tiny
        # model, so the per-chip byte and roofline gates are OFF here
        # (they need the full geometry where matmul weights dominate)
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    max_prompt = max(8, args.seq_len)
    max_seq = max_prompt + args.max_new_tokens
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)
    # trained-model margin profile (same recipe as --quant): tied 4x-gain
    # embedding head so top-2 logit gaps dwarf the all-reduce's f32
    # reassociation noise and the bit-identity gate measures the layout,
    # not argmax tie-breaking
    params["embed"] = params["embed"] * 4.0
    params["head"] = params["embed"].T

    def build(kind, tp_n):
        kw = dict(
            tp=tp_n, num_heads=dims["num_heads"],
            batch_slots=args.batch_slots, max_seq=max_seq,
            temperature=0.0, rng=jax.random.key(1),
        )
        if kind == "paged_int8":
            kw.update(
                kv_layout="paged", cache_dtype=jnp.int8,
                page_size=args.page_size, num_pages=args.kv_pages,
                prefill_chunk=args.prefill_chunk,
            )
        engine, _mesh = tensor_parallel_engine(params, **kw)
        return engine

    requests = synthetic_requests(
        args.serve_requests, vocab_size=dims["vocab_size"],
        max_prompt=max_prompt, min_prompt=max(2, max_prompt // 8),
        rng=np.random.default_rng(0),
    )

    def run_one(engine):
        if args.steps_cap is None:
            _serve_warmup(
                engine, max_seq, requests, vocab_size=dims["vocab_size"]
            )
        results, report = ContinuousBatchingScheduler(
            engine,
            max_new_tokens=args.max_new_tokens,
            step_cap=args.steps_cap,
        ).run(list(requests))
        if args.steps_cap is None:
            assert report.prefill_compiles == 0, (
                f"warmup missed {report.prefill_compiles} prefill shape(s)"
            )
        return {r.uid: r.tokens for r in results}, report

    def per_chip_param_bytes(engine):
        """{device: params bytes resident} from sharding metadata only
        (the ledger's accounting walk — obs/ledger._shard_bytes)."""
        totals = {}
        for leaf in jax.tree_util.tree_leaves(engine.params):
            per_shard, devices = _ledger._shard_bytes(leaf)
            for dev in devices:
                key = str(dev)
                totals[key] = totals.get(key, 0) + per_shard
        return totals

    def _time_decode(engine, steps=5):
        # min over single dispatches — the noise-robust wall estimate on
        # a shared host; the decode program is already compiled (the
        # scheduler run above drove it)
        tokens = np.ones(engine.batch_slots, np.int32)
        pos = np.full(engine.batch_slots, 1, np.int32)
        best = float("inf")
        for _ in range(steps):
            t0 = _time.perf_counter()
            jax.block_until_ready(engine.decode(tokens, pos))
            best = min(best, _time.perf_counter() - t0)
        return best

    def decode_program_verdict(engine):
        """(roofline dict, collective stats) for the compiled decode
        program: the LAST recorded decode signature re-lowered and
        AOT-compiled, post-partitioning cost_analysis flops/bytes (the
        per-chip program — TP=N compiles ~1/N of the matmul work plus
        its collectives) against the reference chip ceilings."""
        prog = engine._decode_jit
        assert prog._sigs, "decode never compiled — the run above is gone"
        sig_args, sig_kwargs = list(prog._sigs.values())[-1]
        compiled = prog._fn.lower(*sig_args, **sig_kwargs).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = ca or {}
        flops = float(ca.get("flops", 0.0) or 0.0)
        nbytes = float(
            ca.get("bytes accessed", ca.get("bytes_accessed", 0.0)) or 0.0
        )
        peak_tflops, peak_gbps, peak_src = reference_peaks()
        roofline = program_roofline(
            flops, nbytes, _time_decode(engine),
            peak_tflops=peak_tflops, peak_hbm_gbps=peak_gbps,
        )
        roofline["peak_source"] = peak_src
        roofline["measured_note"] = (
            "measured_s is informational on a virtual pod (host-core "
            "contention); roofline_s is the gated, deterministic figure"
        )
        coll = comms.collective_stats(
            compiled.as_text(), mesh=engine.mesh
        )
        return roofline, coll

    configs = ("dense_f32", "paged_int8")
    tokens, reports, engines = {}, {}, {}
    for kind in configs:
        for tp_n in (1, tp):
            engine = build(kind, tp_n)
            tokens[(kind, tp_n)], reports[(kind, tp_n)] = run_one(engine)
            engines[(kind, tp_n)] = engine

    bit_identical = {
        kind: tokens[(kind, 1)] == tokens[(kind, tp)] for kind in configs
    }
    param_bytes = {
        f"tp{tp_n}": per_chip_param_bytes(engines[("dense_f32", tp_n)])
        for tp_n in (1, tp)
    }
    per_chip_ratio = round(
        max(param_bytes[f"tp{tp}"].values())
        / max(param_bytes["tp1"].values()),
        4,
    )
    rooflines, collectives = {}, {}
    for kind in configs:
        for tp_n in (1, tp):
            rl, coll = decode_program_verdict(engines[(kind, tp_n)])
            rooflines[(kind, tp_n)] = rl
            if tp_n == tp:
                collectives[kind] = coll
    roofline_ratio = {
        kind: round(
            rooflines[(kind, tp)]["roofline_s"]
            / rooflines[(kind, 1)]["roofline_s"],
            4,
        )
        for kind in configs
    }
    tp_all_reduces = {
        kind: collectives[kind].get(comms.TP_ALL_REDUCE, {}).get("count", 0)
        for kind in configs
    }

    gates = {
        "bit_identical": all(bit_identical.values()),
        "param_bytes_per_chip": per_chip_ratio <= 0.55,
        "decode_roofline_latency": all(
            r < 1.0 for r in roofline_ratio.values()
        ),
    }
    full_run = args.steps_cap is None and not args.small
    assert gates["bit_identical"], (
        f"TP={tp} greedy streams diverged from TP=1: {bit_identical} — "
        "the Megatron layout changed the sampled tokens"
    )
    if full_run:
        assert gates["param_bytes_per_chip"], (
            f"per-chip param bytes at TP={tp} are {per_chip_ratio:.2%} "
            "of TP=1 (> 55%) — the table failed to shard the weights"
        )
        assert gates["decode_roofline_latency"], (
            f"TP={tp} decode roofline did not beat TP=1 on every config "
            f"(ratios {roofline_ratio}) — TP is paying HBM without "
            "buying latency"
        )
        assert all(n >= 1 for n in tp_all_reduces.values()), (
            f"TP decode compiled without a tensor all-reduce "
            f"({tp_all_reduces}) — the weights replicated behind the "
            "table's back"
        )

    def cfg_line(kind, tp_n):
        rep, eng = reports[(kind, tp_n)], engines[(kind, tp_n)]
        return {
            **_serve_line(rep, eng, args, max_prompt=max_prompt,
                          mesh=eng.mesh),
            "decode_roofline": rooflines[(kind, tp_n)],
        }

    line = {
        "metric": "lm_serve_tp_param_bytes_per_chip_ratio",
        # max-over-chips resident param bytes, TP=N over TP=1
        "value": per_chip_ratio,
        "unit": "x",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "tp": tp,
        "layout_rules": _layout.layout_rules_provenance(),
        "model": "synthetic LM, tied embedding head (4x embed gain — "
                 "trained-model margin profile)",
        "dims": dims,
        "max_seq": max_seq,
        "gates": gates,
        "gates_enforced": bool(full_run),
        "tp_param_bytes_per_chip_ratio": per_chip_ratio,
        "param_bytes_per_chip": param_bytes,
        "bit_identical": bit_identical,
        # flat leaf keys so `ddlt obs history --gate` tracks them by name
        "tp_decode_roofline_ms_dense_f32": round(
            rooflines[("dense_f32", tp)]["roofline_s"] * 1e3, 6
        ),
        "tp_decode_roofline_ms_paged_int8": round(
            rooflines[("paged_int8", tp)]["roofline_s"] * 1e3, 6
        ),
        "decode_roofline_ratio_vs_tp1": roofline_ratio,
        "tp_all_reduces_per_decode": tp_all_reduces,
        "collectives": collectives,
        "configs": {
            kind: {f"tp{tp_n}": cfg_line(kind, tp_n) for tp_n in (1, tp)}
            for kind in configs
        },
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps(line))
    report_path = args.report or artifact_name("TP")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    return 0


def _run_spec(args) -> int:
    """Speculative-decoding benchmark: drafter + batched verify vs plain
    f32 decode on identical greedy traffic (the ``SPEC_r{NN}.json``
    artifact).

    Three paged engines over the SAME sharpened tied-head LM (the
    trained-model margin profile ``--quant`` uses — near-tied random-init
    logits would measure argmax tie luck, not drafter quality):

    - ``f32`` — the non-speculative baseline;
    - ``spec_truncated`` — truncated-layer self-draft (first
      ``--draft-layers`` of the shared stack + the shared head: no extra
      weights);
    - ``spec_int8`` — the full-depth int8-weight drafter (QUANT_r10's
      greedy-agreement number paying rent as draft acceptance).

    Both spec runs must produce tokens BIT-IDENTICAL to the baseline
    across the whole workload (the acceptance rule is the verifier's own
    f32 argmax, so this gate is exact, not statistical).  Full (non
    ``--steps-cap``) runs additionally gate the truncated drafter's
    ``decode_tokens_per_sec`` strictly above the baseline's — tokens per
    second of the decode phase alone, where speculation lives; whole-run
    tok/s would dilute the comparison with identical prefill wall.

    Model dims are serving-shaped for the CPU bench host: decode must be
    latency-bound (per-step overhead + bandwidth) as it is on real
    serving hardware, not compute-bound — at full training geometry a
    CPU decode step is matmul-FLOP-bound, a regime where batching K+1
    verify positions multiplies compute instead of amortizing weight
    reads, and which no TPU serving deployment lives in (OBS_r11: decode
    latency-bound on history compute).
    """
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedInferenceEngine,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.spec import SpeculativeDecoder

    dims = dict(num_layers=12, d_model=256, num_heads=8, d_ff=1024,
                vocab_size=8192)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    max_prompt = max(8, args.seq_len)
    max_seq = max_prompt + args.max_new_tokens
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)
    # sharpened tied head — the trained-model margin profile (see
    # _run_quant's rationale): drafter acceptance should measure drafter
    # fidelity, not tie-breaking against iid-Gaussian noise
    params["embed"] = params["embed"] * 4.0
    params["head"] = params["embed"].T

    K = args.draft_tokens
    draft_layers = (
        args.draft_layers
        if args.draft_layers is not None
        else max(1, dims["num_layers"] // 6)
    )

    def build():
        return PagedInferenceEngine(
            params,
            num_heads=dims["num_heads"],
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            page_size=args.page_size,
            num_pages=args.kv_pages,
            prefill_chunk=args.prefill_chunk,
            temperature=0.0,  # greedy: the bit-identical gate needs it
            rng=jax.random.key(1),
        )

    requests = synthetic_requests(
        args.serve_requests, vocab_size=dims["vocab_size"],
        max_prompt=max_prompt, min_prompt=max(2, max_prompt // 2),
        rng=np.random.default_rng(0),
    )

    def run_one(spec_builder=None):
        engine = build()
        sd = spec_builder(engine) if spec_builder is not None else None
        if args.steps_cap is None:
            _serve_warmup(
                engine, max_seq, requests,
                vocab_size=dims["vocab_size"], spec_decoder=sd,
            )
        results, report = ContinuousBatchingScheduler(
            engine,
            max_new_tokens=args.max_new_tokens,
            step_cap=args.steps_cap,
            spec_decoder=sd,
        ).run(list(requests))
        if args.steps_cap is None:
            assert report.prefill_compiles == 0, (
                f"warmup missed {report.prefill_compiles} prefill shape(s)"
            )
        return {r.uid: r.tokens for r in results}, report

    tokens, reports = {}, {}
    tokens["f32"], reports["f32"] = run_one()
    tokens["spec_truncated"], reports["spec_truncated"] = run_one(
        lambda e: SpeculativeDecoder(
            e, drafter="truncated", draft_tokens=K,
            draft_layers=draft_layers,
        )
    )
    tokens["spec_int8"], reports["spec_int8"] = run_one(
        lambda e: SpeculativeDecoder(e, drafter="int8", draft_tokens=K)
    )

    bit_identical = {
        name: tokens[name] == tokens["f32"]
        for name in ("spec_truncated", "spec_int8")
    }
    base_dec = reports["f32"].decode_tokens_per_sec
    speedup = (
        round(reports["spec_truncated"].decode_tokens_per_sec / base_dec, 4)
        if base_dec else None
    )
    gates = {
        "bit_identical": all(bit_identical.values()),
        "spec_decode_speedup": (
            base_dec > 0
            and reports["spec_truncated"].decode_tokens_per_sec > base_dec
        ),
    }
    if args.steps_cap is None:
        assert gates["bit_identical"], (
            "speculative greedy tokens diverged from the non-speculative "
            f"baseline: {bit_identical} — the acceptance rule broke the "
            "decode==full-forward pin"
        )
        spec_dec = reports["spec_truncated"].decode_tokens_per_sec
        assert gates["spec_decode_speedup"], (
            f"truncated-drafter spec decode ({spec_dec} tok/s) did not "
            f"beat the f32 baseline ({base_dec} tok/s)"
        )

    drafters = {
        name: {
            "drafter": reports[name].drafter,
            "draft_tokens": reports[name].draft_tokens,
            "acceptance_rate": reports[name].acceptance_rate,
            "tokens_per_verify": reports[name].tokens_per_verify,
            "decode_tokens_per_sec": reports[name].decode_tokens_per_sec,
            "tokens_per_sec": reports[name].tokens_per_sec,
            "bit_identical": bit_identical[name],
            "draft_step_s": reports[name].draft_step_s,
            "verify_step_s": reports[name].verify_step_s,
        }
        for name in ("spec_truncated", "spec_int8")
    }
    drafters["spec_truncated"]["draft_layers"] = draft_layers

    line = {
        "metric": "lm_serve_spec_decode_speedup",
        # truncated-drafter decode-phase tok/s over the f32 baseline
        "value": speedup,
        "unit": "x",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "model": "synthetic LM, tied embedding head (4x embed gain — "
                 "trained-model margin profile), serving-shaped dims",
        "dims": dims,
        "max_seq": max_seq,
        "page_size": args.page_size,
        "prefill_chunk": args.prefill_chunk,
        "draft_tokens": K,
        "baseline": {
            "decode_tokens_per_sec": base_dec,
            "tokens_per_sec": reports["f32"].tokens_per_sec,
            "decode_step_ms": round(
                reports["f32"].decode_step_s["p50"] * 1e3, 3
            ),
        },
        "drafters": drafters,
        "gates": gates,
        "configs": {
            name: rep.to_dict() for name, rep in reports.items()
        },
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps(line))
    report_path = args.report or artifact_name("SPEC")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    return 0


def _run_obs(args) -> int:
    """Observability benchmark: one merged host+device timeline over the
    f32 and int8-KV serving engines, plus the decode-phase attribution
    QUANT_r10 was missing.

    Runs identical greedy traffic through an f32 paged engine and an
    int8-KV paged engine with the obs tracer enabled inside a
    ``jax.profiler.trace`` window, then:

    - merges the host spans (request lifecycles, prefill chunks, decode
      steps, dispatch-vs-readback) with the device profile onto one
      Chrome-trace timeline (full trace written next to the artifact,
      a digest embedded in it);
    - measures each engine's decode step as per-phase jitted programs
      (page gather / scale dequant / attention+MLP residual) and names
      the phase that explains the int8 regression — the hottest phase
      and its share of the int8 step time;
    - attaches the roofline per-op analysis when the platform's trace
      carries XLA cost-model annotations (TPU; reported absent on CPU);
    - snapshots the metrics registry (TTFT/TPOT/decode-step histograms
      both runs fed) into the artifact.

    Emits ``OBS_r{NN}.json`` — validated against ``obs.schema`` before it
    is written, so the artifact can never drift from what tier-1 checks.
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.obs import (
        MetricsRegistry,
        configure,
        get_registry,
        set_registry,
    )
    from distributeddeeplearning_tpu.obs.profile import (
        attribute_regression,
        decode_phase_breakdown,
        device_analysis,
        profile_and_merge,
        summarize_timeline,
    )
    from distributeddeeplearning_tpu.obs.schema import validate_obs_payload
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedInferenceEngine,
        synthetic_requests,
    )

    dims = dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072,
                vocab_size=32768)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    max_prompt = max(8, args.seq_len)
    max_seq = max_prompt + args.max_new_tokens
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)

    def build(cache_dtype=None):
        return PagedInferenceEngine(
            params,
            num_heads=dims["num_heads"],
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            page_size=args.page_size,
            num_pages=args.kv_pages,
            prefill_chunk=args.prefill_chunk,
            temperature=0.0,
            rng=jax.random.key(1),
            cache_dtype=cache_dtype,
        )

    engines = {"f32": build(), "kv_int8": build(jnp.int8)}
    requests = synthetic_requests(
        args.serve_requests, vocab_size=dims["vocab_size"],
        max_prompt=max_prompt, min_prompt=max(2, max_prompt // 8),
        rng=np.random.default_rng(0),
    )
    smoke = args.steps_cap is not None
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="ddlt-obs-")
    tracer = configure(enabled=False)  # enabled inside the trace window

    def run_one(name, engine):
        with tracer.span(f"obs/serve_{name}"):
            _, report = ContinuousBatchingScheduler(
                engine,
                max_new_tokens=args.max_new_tokens,
                step_cap=args.steps_cap,
            ).run(list(requests))
        if not smoke:
            assert report.prefill_compiles == 0, (
                f"warmup missed {report.prefill_compiles} prefill shape(s)"
            )
        return report

    # warmup OUTSIDE the profiled window: the timeline should show
    # serving, not compilation
    if not smoke:
        for engine in engines.values():
            _serve_warmup(
                engine, max_seq, requests, vocab_size=dims["vocab_size"]
            )
    # the warmup schedulers above rolled their compile-dominated samples
    # into the process registry; the artifact's obs_metrics must reflect
    # the PROFILED runs only, so start it fresh here
    set_registry(MetricsRegistry())
    reports = {}
    breakdowns = {}
    phase_iters = 2 if smoke else 10
    def _windowed():
        for name, engine in engines.items():
            reports[name] = run_one(name, engine)
        with tracer.span("obs/phase_breakdown"):
            for name, engine in engines.items():
                breakdowns[name] = decode_phase_breakdown(
                    engine, iters=phase_iters,
                    warmup=1 if smoke else 2,
                )

    _, _, merged, merged_path = profile_and_merge(
        _windowed, trace_dir=trace_dir, tracer=tracer
    )
    attribution = attribute_regression(
        breakdowns["f32"], breakdowns["kv_int8"]
    )
    # data-driven verdict sentence: artifacts get quoted without their
    # context, so the number's meaning travels with it — including when
    # the regression under test does NOT reproduce (which is exactly the
    # attribution a host-noise-contaminated earlier artifact needs)
    reg_ms = attribution["regression_ms"]
    hp_ms = attribution["hottest_phase_delta_ms"]
    attribution["note"] = (
        f"int8-KV decode {'REGRESSED' if reg_ms > 0 else 'improved'} by "
        f"{abs(reg_ms):.1f} ms vs f32 at full-history steady state on "
        f"this host; the phase that "
        f"{'grew most' if hp_ms > 0 else 'shrank least'} is "
        f"{attribution['hottest_phase']} ({hp_ms:+.1f} ms, "
        f"{attribution['hottest_phase_share_of_step_time']:.1%} of the "
        f"int8 step)"
        + (
            "" if reg_ms > 0 else
            " — a gap larger than this in another artifact's decode "
            "step (e.g. QUANT's) was not the quantized math"
        )
    )

    line = {
        "metric": "lm_serve_obs_int8_decode_hottest_phase_share",
        # the named hottest phase's share of the int8 decode step — the
        # attribution number ROADMAP Open item 2 (fused int8 kernels)
        # gates its fix against
        "value": attribution["hottest_phase_share_of_step_time"],
        "unit": "fraction_of_step",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "max_seq": max_seq,
        "page_size": args.page_size,
        "prefill_chunk": args.prefill_chunk,
        "regression_attribution": attribution,
        "decode_breakdown": breakdowns,
        "timeline": summarize_timeline(merged),
        "merged_trace_path": merged_path,
        # the profiler window spans BOTH engines' prefills + decodes plus
        # the phase-timing loops, so there is no single-engine step count
        # to normalize by: steps=1 makes every per-step roofline figure a
        # per-WINDOW total, and the scope note travels with the numbers
        "device_analysis": {
            **device_analysis(trace_dir, steps=1),
            "scope": (
                "whole --obs profile window (f32 + int8 serve runs + "
                "phase-timing loops); per-step keys are per-window "
                "totals, not per-decode-step"
            ),
        },
        "serve_reports": {
            name: _serve_line(rep, engines[name], args,
                              max_prompt=max_prompt)
            for name, rep in reports.items()
        },
        "obs_metrics": get_registry().snapshot(),
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    # self-check before emitting: the artifact the README documents is
    # the artifact tier-1 validates — drift fails HERE, not months later
    validate_obs_payload(line)
    print(json.dumps(line))
    report_path = args.report or artifact_name("OBS")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[obs] report -> {report_path}", file=sys.stderr)
    print(f"[obs] merged chrome trace -> {merged_path}", file=sys.stderr)
    return 0


def _run_obs_fleet(args) -> int:
    """Fleet-observability benchmark: a chaos fleet whose recovery is
    VISIBLE, not just survived.

    Runs a 2-replica (``--serve-replicas``) paged-engine fleet through
    ``--obs-fleet-spec`` (a replica death + a decode stall by default)
    with distributed request tracing on: the router mints one trace id
    per request, workers tag every scheduler span with (trace,
    replica) and export per-process Chrome-trace shards, and
    ``obs.fleet`` merges the shards onto the router clock into
    ``fleet.trace.json``.  Emits ``OBS_FLEET_r{NN}.json`` gated on:

    - **failover_traceable**: at least one requeued request's chain in
      the MERGED timeline shows the full story — served on the dying
      replica → ``fleet/replica_died`` → ``fleet/request_requeued`` →
      completion on a different process — under one trace id;
    - **percentiles_merge_exact**: the artifact's fleet TTFT/TPOT
      percentile blocks equal a from-scratch recomputation off the
      committed per-replica histogram buckets, in any merge order
      (bucket merging is exact; averaging percentiles would not be);
    - **zero_lost_requests**: the chaos run loses nothing;
    - **slo_pass**: the declarative ``--slo`` spec holds over the
      merged fleet metrics.

    The artifact is validated against the registered ``OBS_FLEET_*``
    schema before it is written.
    """
    import os
    import tempfile

    import jax
    import numpy as np

    from distributeddeeplearning_tpu.obs.fleet import (
        SLOSpec,
        fleet_latency,
        observe_fleet,
    )
    from distributeddeeplearning_tpu.obs.registry import merge_states
    from distributeddeeplearning_tpu.obs.schema import (
        validate_obs_fleet_payload,
    )
    from distributeddeeplearning_tpu.serve import (
        ReplicaSpec,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.utils import faults as faults_mod

    if not any(
        s.kind == "replica_death"
        for s in faults_mod.parse_spec(args.obs_fleet_spec)
    ):
        print(
            "[obs-fleet] --obs-fleet-spec must inject a replica_death — "
            "the artifact's whole point is a traceable failover",
            file=sys.stderr,
        )
        return 1
    slo = SLOSpec.parse(args.slo)
    dims = dict(num_layers=4, d_model=256, num_heads=8, d_ff=1024,
                vocab_size=8193)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    max_prompt = max(8, args.seq_len if not args.small else 12)
    new_tokens = args.obs_fleet_new_tokens
    max_seq = max_prompt + new_tokens
    spec = ReplicaSpec(
        model=dict(max_len=max_seq, **dims),
        seed=0,
        num_heads=dims["num_heads"],
        batch_slots=args.batch_slots,
        max_seq=max_seq,
        kv_layout="paged",
        page_size=args.page_size,
        num_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        temperature=0.0,
        max_new_tokens=new_tokens,
    )
    requests = synthetic_requests(
        args.obs_fleet_requests, vocab_size=dims["vocab_size"],
        max_prompt=max_prompt, min_prompt=max(2, max_prompt // 8),
        rng=np.random.default_rng(0),
    )
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="ddlt-obs-fleet-")
    print(
        f"[obs-fleet] chaos fleet: {args.serve_replicas} replicas, "
        f"{len(requests)} requests, faults={args.obs_fleet_spec}",
        file=sys.stderr,
    )
    view = observe_fleet(
        spec, requests,
        replicas=args.serve_replicas,
        trace_dir=trace_dir,
        faults=args.obs_fleet_spec,
        slo=slo,
        max_restarts=args.serve_max_restarts,
    )
    report = view["fleet_report"]

    # gate (a): the failover is traceable end-to-end under one trace id
    chains_ok = sum(1 for c in view["failover"].values() if c["ok"])
    failover_traceable = report.replica_deaths >= 1 and chains_ok >= 1

    # gate (b): the fleet percentiles must be EXACTLY reproducible from
    # the committed per-replica buckets — recomputed here in reversed
    # merge order, so order-dependence would fail too
    recomputed = fleet_latency(
        merge_states(list(reversed(view["per_replica_metrics"])))
    )
    merge_exact = recomputed == view["fleet_latency"]

    gates = {
        "failover_traceable": bool(failover_traceable),
        "percentiles_merge_exact": bool(merge_exact),
        "zero_lost_requests": report.lost_requests == 0,
        "slo_pass": bool(view["slo"]["pass"]),
    }
    line = {
        "metric": "serve_fleet_obs_ttft_p99_s",
        # the headline is the number the SLO layer gates: fleet-level
        # TTFT p99 from bucket-merged worker histograms, measured UNDER
        # chaos (the failover cost is inside it, not hidden per-replica)
        "value": view["fleet_latency"]["ttft_s"]["p99"],
        "unit": "s",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "faults_spec": args.obs_fleet_spec,
        "replicas": args.serve_replicas,
        "requests": len(requests),
        "max_new_tokens": new_tokens,
        "model_dims": dims,
        "merged_trace_path": view["merged_trace_path"],
        "timeline": view["timeline"],
        "failover": view["failover"],
        "failover_chains_ok": chains_ok,
        "fleet_latency": view["fleet_latency"],
        "fleet_latency_recomputed": recomputed,
        "fleet_metrics": view["fleet_metrics"],
        "per_replica_metrics": view["per_replica_metrics"],
        "flight_recorder_dumps": len(view["flight_recorder_dumps"]),
        "flight_recorder_dump_reasons": sorted(
            {d.get("reason") for d in view["flight_recorder_dumps"]}
        ),
        "slo": view["slo"],
        "gates": gates,
        "fleet_report": report.to_dict(),
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    # self-check before emitting: the artifact the README documents is
    # the artifact tier-1 validates — drift fails HERE, not months later
    validate_obs_fleet_payload(line)
    print(json.dumps({
        k: line[k] for k in (
            "metric", "value", "unit", "vs_baseline", "faults_spec",
            "failover_chains_ok", "gates",
        )
    }))
    report_path = args.report or artifact_name("OBS_FLEET")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[obs-fleet] report -> {report_path}", file=sys.stderr)
    print(
        f"[obs-fleet] merged fleet trace -> {view['merged_trace_path']}",
        file=sys.stderr,
    )
    if not all(gates.values()):
        print(f"[obs-fleet] GATES FAILED: {gates}", file=sys.stderr)
        return 1
    return 0


def _run_faults(args) -> int:
    """Chaos benchmark: the REAL ``ddlt train --max-restarts`` supervisor
    driven over an injected fault schedule, measured against the identical
    clean run.

    Both runs are child processes (process-per-attempt is also what real
    supervision looks like — and repeated in-process workload re-entry
    accumulates enough XLA/orbax thread churn to destabilize the CPU
    runtime).  The ``RESILIENCE_*.json`` artifact answers the question the
    resilience layer exists for: what does surviving a realistic fault mix
    COST?  It records the faults injected (parsed from the child's
    injection log), the recoveries taken (supervisor restarts, anomalous
    updates skipped), the steps re-done after restart-from-checkpoint (the
    supervisor's own accounting), and the headline
    ``recovery_overhead_pct`` — faulted wall vs clean wall, both runs
    checkpointing at the same cadence so the overhead isolates *recovery*,
    not checkpointing.
    """
    import os
    import re
    import subprocess
    import tempfile
    import time as _time

    import jax

    epochs, spe = 3, 5
    total_steps = epochs * spe
    work_dir = tempfile.mkdtemp(prefix="ddlt-faults-")
    model = args.model if args.model != "lm" else "resnet18"

    def train_argv(ckpt_dir):
        return [
            sys.executable, "-m", "distributeddeeplearning_tpu.cli.main",
            "train", "imagenet",
            "--max-restarts", str(args.faults_max_restarts),
            "--model", model,
            "--data_format", "synthetic",
            "--epochs", str(epochs),
            "--steps_per_epoch", str(spe),
            "--batch_size", str(args.batch_size),
            "--image_size", str(args.image_size),
            "--num_classes", "11",
            # CPU chaos runs; bf16 emulation just adds wall
            "--compute_dtype", "float32",
            "--checkpoint_every_steps", "3",
            "--seed", "0",
            "--skip_nonfinite", "true",
            "--anomaly_max_consecutive", "5",
            "--save_filepath", ckpt_dir,
        ]

    def run_child(ckpt_dir, spec):
        env = dict(os.environ)
        env.pop("DDLT_FAULTS", None)
        if spec:
            env["DDLT_FAULTS"] = spec
        t0 = _time.perf_counter()
        proc = subprocess.run(
            train_argv(ckpt_dir), env=env, text=True,
            capture_output=True, timeout=1800,
        )
        wall = _time.perf_counter() - t0
        sys.stderr.write(proc.stderr)
        return proc, wall

    clean, clean_wall = run_child(f"{work_dir}/clean", None)
    if clean.returncode != 0:
        print(
            f"[faults] clean reference run failed (rc={clean.returncode})",
            file=sys.stderr,
        )
        return 1
    faulted, faulted_wall = run_child(f"{work_dir}/faulted", args.faults_spec)

    # the supervisor's completion line carries the recovery accounting
    m = re.search(
        r"completed at step (\d+): restarts=(\d+) redone_steps=(\d+) "
        r"anomalous_steps=(\d+)",
        faulted.stdout,
    )
    final_step = int(m.group(1)) if m else None
    injected = [
        {"kind": k, "step": (int(s) if s.isdigit() else None)}
        for k, s in re.findall(
            r"FAULT INJECTED: (\w+)\S* at step (\S+)", faulted.stderr
        )
    ]
    skipped_updates = len(
        re.findall(r"anomalous step \d+ .*update skipped", faulted.stderr)
    )

    overhead_pct = round(100.0 * (faulted_wall - clean_wall) / clean_wall, 2)
    line = {
        "metric": "resilience_chaos_recovery_overhead_pct",
        "value": overhead_pct,
        "unit": "%",
        "vs_baseline": None,
        "faults_spec": args.faults_spec,
        "faults_injected": injected,
        "faults_count": len(injected),
        "restarts": int(m.group(2)) if m else None,
        "redone_steps": int(m.group(3)) if m else None,
        "anomalous_steps_skipped": skipped_updates,
        "total_steps": total_steps,
        "final_step": final_step,
        "completed_exact": final_step == total_steps,
        "child_rc": faulted.returncode,
        "clean_wall_s": round(clean_wall, 2),
        "faulted_wall_s": round(faulted_wall, 2),
        "wall_includes_process_start": True,  # both runs pay it equally
        "model": model,
        "supervisor": f"ddlt train --max-restarts {args.faults_max_restarts}",
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps(line))
    report_path = args.report or artifact_name("RESILIENCE")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[faults] report -> {report_path}", file=sys.stderr)
    return 0 if line["completed_exact"] and faulted.returncode == 0 else 1


def _run_goodput(args) -> int:
    """Goodput-ledger chaos benchmark — the ``GOODPUT_r{NN}.json``
    artifact: a short training run under the REAL ``ddlt train
    --max-restarts`` supervisor with an injected preemption AND an
    anomaly abort, its wall classified 100% by the goodput ledger
    (``obs/goodput.py``), stitched across the restart incarnations.
    Gates (return code 1 on violation):

    - **residual_under_limit**: the category sum covers total wall
      within the ±2% unaccounted-time gate (a ledger that lost time
      reports optimistic goodput — that is the bug class the gate
      exists for);
    - **redone_matches_supervisor**: the ledger's ``steps_redone``
      count equals the supervisor's own ``redone_steps`` accounting
      EXACTLY (two independent implementations of "which steps were
      re-executed" must agree);
    - **recovery_observed**: the chaos run shows nonzero ``recovery``
      wall and at least one restart — a fault-free artifact would
      prove nothing about restart durability;
    - **completed_exact**: the run still reaches the exact final step;
    - **trajectory_green**: the perf-history tracker
      (``obs/history.py``) runs green over every committed artifact —
      the trajectory digest travels inside this artifact.

    The default fault spec injects ``preempt@6`` (emergency checkpoint
    at the exact step → zero redone work, pure recovery gap) and three
    consecutive ``nan_loss`` steps ending ON the last step (anomaly
    abort at step 15 with the newest verified checkpoint at 12 → exactly
    2 redone steps), so both restart flavors land in one ledger.
    """
    import os
    import re
    import subprocess
    import tempfile
    import time as _time

    import jax

    from distributeddeeplearning_tpu.obs import goodput as goodput_mod
    from distributeddeeplearning_tpu.obs import history as history_mod
    from distributeddeeplearning_tpu.obs.schema import (
        SchemaError,
        validate_goodput_payload,
    )

    epochs, spe, every = 3, 5, 4
    total_steps = epochs * spe
    work_dir = tempfile.mkdtemp(prefix="ddlt-goodput-")
    ledger_path = os.path.join(work_dir, "goodput.jsonl")
    ckpt_dir = os.path.join(work_dir, "ckpt")
    # accounting bench, not a throughput bench: tiny dims keep the CPU
    # chaos run short while every category still accrues real wall
    batch, image = (4, 24) if args.small else (8, 32)

    argv = [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli.main",
        "train", "imagenet",
        "--max-restarts", str(args.goodput_max_restarts),
        "--model", "resnet18",
        "--data_format", "synthetic",
        "--epochs", str(epochs),
        "--steps_per_epoch", str(spe),
        "--batch_size", str(batch),
        "--image_size", str(image),
        "--num_classes", "11",
        "--compute_dtype", "float32",
        "--checkpoint_every_steps", str(every),
        "--seed", "0",
        "--skip_nonfinite", "true",
        "--anomaly_max_consecutive", "3",
        "--save_filepath", ckpt_dir,
        "--goodput_path", ledger_path,
    ]
    env = dict(os.environ)
    env.pop("DDLT_FAULTS", None)
    if args.goodput_spec:
        env["DDLT_FAULTS"] = args.goodput_spec
    print(
        f"[goodput] {total_steps}-step chaos run under the supervisor "
        f"(faults: {args.goodput_spec or 'none'})", file=sys.stderr,
    )
    t0 = _time.perf_counter()
    proc = subprocess.run(
        argv, env=env, text=True, capture_output=True, timeout=1800,
    )
    child_wall = _time.perf_counter() - t0
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        print(
            f"[goodput] supervised run failed (rc={proc.returncode})",
            file=sys.stderr,
        )
        return 1

    m = re.search(
        r"completed at step (\d+): restarts=(\d+) redone_steps=(\d+) "
        r"anomalous_steps=(\d+)",
        proc.stdout,
    )
    final_step = int(m.group(1)) if m else None
    sup_restarts = int(m.group(2)) if m else None
    sup_redone = int(m.group(3)) if m else None
    anomalous = int(m.group(4)) if m else None

    merged = goodput_mod.stitch(ledger_path)
    ledger = goodput_mod.summarize_ledger(merged)

    # the perf trajectory over every committed artifact rides along:
    # the GOODPUT artifact is where goodput-over-time and perf-over-
    # revisions meet
    points = history_mod.load_points(".")
    timeline = history_mod.build_timeline(points)
    regressions = history_mod.check_gates(timeline)
    trajectory = history_mod.timeline_digest(timeline, regressions)

    gates = {
        "residual_under_limit": bool(ledger["residual_under_limit"]),
        "redone_matches_supervisor": (
            sup_redone is not None
            and ledger["counts"].get("steps_redone") == sup_redone
        ),
        "recovery_observed": (
            ledger["seconds"]["recovery"] > 0.0
            and (sup_restarts or 0) >= 1
        ),
        "completed_exact": final_step == total_steps,
        "trajectory_green": bool(trajectory["green"]),
    }
    line = {
        "metric": "train_goodput_fraction",
        "value": ledger["goodput_fraction"],
        "unit": "fraction",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
        "faults_spec": args.goodput_spec,
        "model": "resnet18",
        "total_steps": total_steps,
        "child_wall_s": round(child_wall, 2),
        # the ledger accounts the FIT (first segment begin -> last end);
        # process boot/teardown around it is not training wall
        "wall_includes_process_start": False,
        "supervisor": {
            "max_restarts": args.goodput_max_restarts,
            "restarts": sup_restarts if sup_restarts is not None else -1,
            "redone_steps": sup_redone if sup_redone is not None else -1,
            "anomalous_steps": anomalous,
            "final_step": final_step,
            "cmd": f"ddlt train --max-restarts {args.goodput_max_restarts}",
        },
        "ledger": ledger,
        "segments": merged["segment_rows"],
        "restart_rows": merged["restart_rows"],
        "trajectory": trajectory,
        "gates": gates,
    }
    try:
        validate_goodput_payload(line)
    except SchemaError as exc:
        print(f"[goodput] artifact failed its own schema: {exc}",
              file=sys.stderr)
        return 1
    print(json.dumps({
        k: line[k] for k in (
            "metric", "value", "unit", "bench_revision", "platform",
            "virtual_pod", "faults_spec", "gates",
        )
    }))
    report_path = args.report or artifact_name("GOODPUT")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[goodput] report -> {report_path}", file=sys.stderr)
    for name, ok in gates.items():
        if not ok:
            print(f"[goodput] GATE FAILED: {name}", file=sys.stderr)
    print(
        f"[goodput] goodput_fraction={ledger['goodput_fraction']} "
        f"unaccounted_pct={ledger['unaccounted_pct']} "
        f"recovery_s={ledger['seconds']['recovery']} "
        f"steps_redone={ledger['counts'].get('steps_redone')} "
        f"(supervisor {sup_redone})", file=sys.stderr,
    )
    return 0 if all(gates.values()) else 1


def _run_attrib(args) -> int:
    """Attribution benchmark (``obs/attrib.py`` + ``obs/ledger.py``):
    run the serving engines (f32 dense, f32 paged, int8 paged), a
    speculative decoder and a real ``Trainer`` fit in one process, then
    emit the ``ATTRIB_r{NN}.json`` artifact — per-program
    ``cost_analysis()`` flops/bytes + ``memory_analysis()`` residency,
    the HBM ledger's owner totals reconciled against the process's
    ACTUAL live device bytes, per-phase straggler timing from the run's
    own tracer shards, the analytic compute-vs-collective split for the
    train step, and a ledger-forecast admission demo.  Gates (rc 1):

    - **programs_covered**: every tracked compiled program resolves a
      cost row on this backend (CPU included — attribution is tier-1);
    - **owner_totals_match_live**: ledger owner totals sum to the
      process's live device bytes within 1%;
    - **residual_under_limit**: unaccounted HBM ≤ 5% (bytes nobody owns
      are how OOMs arrive undiagnosed);
    - **forecast_backpressure**: with the ledger capacity sized for ~1
      in-flight request, the scheduler serves every request to
      completion by QUEUEING at predicted-headroom exhaustion — zero
      errors, committed bytes never past capacity (no mid-decode OOM
      path);
    - **trajectory_green**: ``ddlt obs history`` gates green over every
      committed artifact (the digest rides inside this one).
    """
    import itertools
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.data.synthetic import SyntheticDataset
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.obs import attrib as attrib_mod
    from distributeddeeplearning_tpu.obs import history as history_mod
    from distributeddeeplearning_tpu.obs.ledger import HBMLedger
    from distributeddeeplearning_tpu.obs.schema import (
        SchemaError,
        validate_attrib_payload,
    )
    from distributeddeeplearning_tpu.obs.trace import configure
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.parallel.sharding import shard_batch
    from distributeddeeplearning_tpu.serve.engine import (
        InferenceEngine,
        PagedInferenceEngine,
        _register_engine_owners,
    )
    from distributeddeeplearning_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.spec.decode import SpeculativeDecoder
    from distributeddeeplearning_tpu.train.loop import Trainer, TrainerConfig
    from distributeddeeplearning_tpu.train.schedule import goyal_lr_schedule
    from distributeddeeplearning_tpu.train.state import (
        create_train_state,
        sgd_momentum,
    )
    from distributeddeeplearning_tpu.train.step import build_train_step

    small = args.small
    # the run's own tracer feeds the straggler block (per-phase span
    # durations); annotate=False keeps the device profiler out of it
    tracer = configure(enabled=True, annotate=False)

    # ---- serve phase: three engine configs + a speculative decoder ----
    dims = dict(
        num_layers=2, d_model=64 if not small else 32, num_heads=4,
        d_ff=128 if not small else 64, vocab_size=509,
    )
    max_seq = 64
    n_req = 8 if small else 16
    new_tokens = 6 if small else 10
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)
    nh = dims["num_heads"]
    dense = InferenceEngine(
        params, num_heads=nh, batch_slots=4, max_seq=max_seq,
    )
    paged = PagedInferenceEngine(
        params, num_heads=nh, batch_slots=4, max_seq=max_seq,
        page_size=16, prefill_chunk=16,
    )
    paged_int8 = PagedInferenceEngine(
        params, num_heads=nh, batch_slots=4, max_seq=max_seq,
        page_size=16, prefill_chunk=16, cache_dtype=jnp.int8,
    )
    reqs = synthetic_requests(
        n_req, vocab_size=dims["vocab_size"], max_prompt=24,
        shared_prefix_len=8, rng=np.random.default_rng(0),
    )
    print("[attrib] serving synthetic traffic on 3 engine configs",
          file=sys.stderr)
    for eng in (dense, paged, paged_int8):
        ContinuousBatchingScheduler(
            eng, max_new_tokens=new_tokens,
        ).run(list(reqs))
    decoder = SpeculativeDecoder(paged, drafter="truncated", draft_tokens=2)
    ContinuousBatchingScheduler(
        paged, max_new_tokens=new_tokens, spec_decoder=decoder,
    ).run(list(reqs))
    measured = {
        "serve.dense.float32.decode": attrib_mod._time_decode(dense),
        "serve.paged.float32.decode": attrib_mod._time_decode(paged),
        "serve.paged.int8.decode": attrib_mod._time_decode(paged_int8),
    }

    # ---- train phase: a real Trainer fit (registers params/opt_state/
    # batch_stats on the ledger and the train step in the cost registry)
    steps, batch, img = (2, 4, (24, 24, 3)) if small else (3, 8, (32, 32, 3))
    mesh = create_mesh(MeshSpec())
    model = get_model("resnet18", num_classes=10, dtype=jnp.float32)
    tx = sgd_momentum(goyal_lr_schedule(0.05, 1, steps_per_epoch=100))
    state = create_train_state(jax.random.key(0), model, (batch, *img), tx)
    step = build_train_step(mesh, state, compute_dtype=jnp.float32)
    ds = SyntheticDataset(
        length=batch * (steps + 2), image_shape=img, num_classes=10,
    )
    trainer = Trainer(
        mesh, step,
        config=TrainerConfig(
            epochs=1, steps_per_epoch=steps, global_batch_size=batch,
            log_every=10**9, prefetch=0,
        ),
    )
    print(f"[attrib] {steps}-step trainer fit (resnet18)", file=sys.stderr)
    state, _ = trainer.fit(
        state, itertools.cycle(ds.batches(batch))
    )
    # steady-state step wall (post-compile): time direct step calls,
    # then re-point the trainer's ledger provider at the LIVE state
    # (the timed calls donated the fit's final state)
    host_batch = next(iter(ds.batches(batch)))
    dev_batch = shard_batch(mesh, host_batch)
    walls = []
    for _ in range(3):
        t0 = _time.perf_counter()
        state, _ = trainer.train_step(state, dev_batch)
        jax.block_until_ready(state.params)
        walls.append(_time.perf_counter() - t0)
    trainer._obs_state = state
    measured["train.step.implicit"] = min(walls)

    # ---- forecast-backpressure demo: capacity for ~1 request ----------
    demo_ledger = HBMLedger()
    demo_engine = PagedInferenceEngine(
        params, num_heads=nh, batch_slots=4, max_seq=max_seq,
        page_size=16, prefill_chunk=16,
    )
    _register_engine_owners(demo_engine, demo_ledger)
    demo_reqs = synthetic_requests(
        6, vocab_size=dims["vocab_size"], max_prompt=24,
        rng=np.random.default_rng(1),
    )
    worst = max(
        demo_engine.admit_bytes(len(r.prompt), new_tokens)
        for r in demo_reqs
    )
    capacity = demo_ledger.committed_bytes() + worst + demo_engine._page_bytes
    demo_ledger.set_capacity(capacity)
    _, demo_report = ContinuousBatchingScheduler(
        demo_engine, max_new_tokens=new_tokens, hbm_ledger=demo_ledger,
    ).run(list(demo_reqs))
    forecast_ok = (
        demo_report.errors == 0
        and demo_report.requests == len(demo_reqs)
        and demo_ledger.peak_committed_bytes <= capacity
        and demo_ledger.peak_committed_bytes > 0
    )
    forecast_demo = {
        "capacity_bytes": capacity,
        "request_worst_case_bytes": worst,
        "peak_committed_bytes": demo_ledger.peak_committed_bytes,
        "requests": demo_report.requests,
        "errors": demo_report.errors,
        "finish_reasons": demo_report.finish_reasons,
        "backpressure_held": forecast_ok,
    }

    # ---- the attribution frame ----------------------------------------
    peak_tflops, peak_gbps, peaks_source = attrib_mod.reference_peaks()
    report = attrib_mod.build_report(
        memory=True, measured_step_s=measured,
        peak_tflops=peak_tflops, peak_hbm_gbps=peak_gbps,
    )
    straggler = attrib_mod.straggler_report([tracer.to_chrome_trace()])
    train_row = report["programs"].get("train.step.implicit") or {}
    params_bytes = report["ledger"]["owners"].get("params", {}).get(
        "bytes", 0
    )
    n_dev = jax.device_count()
    split = attrib_mod.compute_collective_split(
        float(train_row.get("flops") or 0.0),
        # analytic ring-allreduce wire bytes for the implicit gradient
        # sync: 2 · params · (n-1)/n per step
        2.0 * params_bytes * (n_dev - 1) / max(n_dev, 1),
        peak_flops=peak_tflops * 1e12,
        interconnect_gbps=200.0,  # labeled reference figure, see below
        measured_step_s=measured.get("train.step.implicit"),
    )
    split["interconnect_source"] = "reference-200GBps"
    split["devices"] = n_dev

    points = history_mod.load_points(".")
    timeline = history_mod.build_timeline(points)
    regressions = history_mod.check_gates(timeline)
    trajectory = history_mod.timeline_digest(timeline, regressions)

    gates = {
        **report["gates"],
        "forecast_backpressure": forecast_ok,
        "trajectory_green": bool(trajectory["green"]),
    }
    line = {
        "metric": "attrib_programs_covered",
        "value": report["programs_covered"],
        "unit": "programs",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
        "programs": report["programs"],
        "programs_covered": report["programs_covered"],
        "owner_match_pct": report["owner_match_pct"],
        "unaccounted_hbm_pct": report["unaccounted_hbm_pct"],
        "peaks_source": peaks_source,
        "measured_step_s": {
            k: round(v, 6) for k, v in measured.items()
        },
        "ledger": report["ledger"],
        "straggler": straggler,
        "train_split_estimate": split,
        "forecast_demo": forecast_demo,
        "trajectory": trajectory,
        "gates": gates,
    }
    try:
        validate_attrib_payload(line)
    except SchemaError as exc:
        print(f"[attrib] artifact failed its own schema: {exc}",
              file=sys.stderr)
        return 1
    print(json.dumps({
        k: line[k] for k in (
            "metric", "value", "unit", "bench_revision", "platform",
            "virtual_pod", "unaccounted_hbm_pct", "owner_match_pct",
            "gates",
        )
    }))
    report_path = args.report or artifact_name("ATTRIB")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[attrib] report -> {report_path}", file=sys.stderr)
    for name, ok in gates.items():
        if not ok:
            print(f"[attrib] GATE FAILED: {name}", file=sys.stderr)
    return 0 if all(gates.values()) else 1


def _run_serve_faults(args) -> int:
    """Serving chaos benchmark: the supervised replica fleet
    (``serve/fleet.py``) driven through an injected serve-side fault
    schedule, measured against the identical fault-free fleet.

    The ``SERVE_RESILIENCE_*.json`` artifact answers the question the
    serving resilience layer exists for: what does surviving replica
    death, decode NaNs, stalls and shedding COST, and does the traffic
    notice?  Gates (return code 1 on violation):

    - **zero lost requests**: every request touched by ``replica_death``
      is requeued and completes (``lost_requests == 0``);
    - **bit-identical failover**: every request that completes OK in the
      faulted run carries EXACTLY the fault-free run's greedy tokens —
      failover continuation (prompt + streamed prefix) is not allowed to
      change the output;
    - **quarantine precision**: only the ``decode_nan``-poisoned
      request(s) fail — exactly as many errors as ``decode_nan`` entries
      in the spec;
    - **bounded recovery overhead**: faulted wall vs clean wall under
      ``--serve-overhead-limit`` % (spawn/compile of the restarted
      replica overlaps surviving replicas' decode, so the fleet pays far
      less than one replica's cold start).

    Both runs use the same spec, seeds and traffic, so the delta is
    *recovery*, not workload.
    """
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.obs import trace as trace_mod
    from distributeddeeplearning_tpu.serve import (
        ReplicaSpec,
        serve_fleet,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.utils import faults as faults_mod

    dims = dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072,
                vocab_size=32768)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    max_prompt = max(8, args.seq_len)
    new_tokens = args.serve_faults_new_tokens
    max_seq = max_prompt + new_tokens
    spec = ReplicaSpec(
        model=dict(max_len=max_seq, **dims),
        seed=0,
        num_heads=dims["num_heads"],
        batch_slots=args.batch_slots,
        max_seq=max_seq,
        kv_layout="paged",
        page_size=args.page_size,
        num_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        temperature=0.0,  # greedy: the bit-identical gate needs it
        max_new_tokens=new_tokens,
    )
    requests = synthetic_requests(
        args.serve_faults_requests, vocab_size=dims["vocab_size"],
        max_prompt=max_prompt, min_prompt=max(2, max_prompt // 8),
        rng=np.random.default_rng(0),
    )
    n_nan = sum(
        1 for s in faults_mod.parse_spec(args.serve_faults_spec)
        if s.kind == "decode_nan"
    )

    def run_fleet(faults_text):
        return serve_fleet(
            spec, requests,
            replicas=args.serve_replicas,
            max_restarts=args.serve_max_restarts,
            faults=faults_text,
        )

    # Warmup fleet (discarded): the FIRST fleet of the process pays
    # one-time costs its successor never sees again — OS page-cache
    # warming of the jax wheels every spawned worker re-imports, and the
    # persistent-compilation-cache population the workers share.  Without
    # this the clean run (always first) is systematically slower and the
    # overhead reads negative.
    warm = requests[: min(4, len(requests))]
    print(
        f"[serve-faults] warmup fleet ({len(warm)} requests, discarded)",
        file=sys.stderr,
    )
    serve_fleet(
        spec, warm, replicas=args.serve_replicas, faults="",
    )
    print(
        f"[serve-faults] clean fleet: {args.serve_replicas} replicas, "
        f"{args.serve_faults_requests} requests", file=sys.stderr,
    )
    clean_res, clean_rep = run_fleet("")
    if clean_rep.completed_ok != len(requests):
        print(
            f"[serve-faults] clean fleet run degraded "
            f"({clean_rep.finish_reasons}) — no baseline to compare",
            file=sys.stderr,
        )
        return 1
    # router-side fleet events land on the obs timeline; record the
    # faulted run's so the artifact carries the recovery story
    tracer = trace_mod.set_tracer(
        trace_mod.Tracer(enabled=True, annotate=False)
    )
    try:
        print(
            f"[serve-faults] chaos fleet: {args.serve_faults_spec}",
            file=sys.stderr,
        )
        fault_res, fault_rep = run_fleet(args.serve_faults_spec)
    finally:
        trace_mod.set_tracer(trace_mod.Tracer(enabled=False))
    fleet_events: dict = {}
    for ev in tracer.events:
        name = ev.get("name", "")
        if name.startswith("fleet/"):
            fleet_events[name] = fleet_events.get(name, 0) + 1

    # Overhead is a WALL-TIME ratio, and wall time on a shared/throttled
    # host swings far more than the recovery cost being measured (the
    # same clean fleet has been observed at 20 s and 33 s minutes apart).
    # Per side, take the MIN wall over `--serve-faults-trials` runs:
    # contention only ever ADDS time, so the min is the least-noisy
    # estimate of each side's true cost.  Correctness gates (tokens,
    # finish reasons, losses) come from the FIRST pair — greedy decode
    # makes repeats token-identical anyway.
    clean_walls = [clean_rep.wall_s]
    fault_walls = [fault_rep.wall_s]
    for trial in range(1, args.serve_faults_trials):
        print(
            f"[serve-faults] wall trial {trial + 1}/"
            f"{args.serve_faults_trials}", file=sys.stderr,
        )
        # the first pair ran clean-then-faulted; alternate the order on
        # extra trials so a slowly-relaxing host throttle cannot keep
        # handing the same side the better phase
        order = (
            ("", args.serve_faults_spec)
            if trial % 2 == 0
            else (args.serve_faults_spec, "")
        )
        for spec_text in order:
            _, rep = run_fleet(spec_text)
            (clean_walls if spec_text == "" else fault_walls).append(
                rep.wall_s
            )
    clean_wall = min(clean_walls)
    fault_wall = min(fault_walls)

    clean_tokens = {r.uid: list(r.tokens) for r in clean_res}
    mismatched = [
        r.uid
        for r in fault_res
        if r.finish_reason in ("eos", "length")
        and list(r.tokens) != clean_tokens[r.uid]
    ]
    poisoned = [
        r.uid for r in fault_res
        if r.finish_reason == "error"
        and "non-finite" in (r.error or "")
    ]
    overhead_pct = round(
        100.0 * (fault_wall - clean_wall) / clean_wall, 2
    )
    gates = {
        "zero_lost_requests": fault_rep.lost_requests == 0,
        "tokens_bit_identical": not mismatched,
        "only_poisoned_failed": (
            fault_rep.errors == len(poisoned) == n_nan
        ),
        "recovery_overhead_under_limit": (
            overhead_pct < args.serve_overhead_limit
        ),
    }
    line = {
        "metric": "serve_fleet_chaos_recovery_overhead_pct",
        "value": overhead_pct,
        "unit": "%",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "faults_spec": args.serve_faults_spec,
        "replicas": args.serve_replicas,
        "max_restarts": args.serve_max_restarts,
        "requests": args.serve_faults_requests,
        "max_new_tokens": new_tokens,
        "max_prompt": max_prompt,
        "model_dims": dims,
        "recovery_overhead_pct": overhead_pct,
        "overhead_limit_pct": args.serve_overhead_limit,
        "wall_trials": args.serve_faults_trials,
        "clean_wall_s": round(clean_wall, 4),
        "faulted_wall_s": round(fault_wall, 4),
        "clean_walls_s": [round(w, 4) for w in clean_walls],
        "faulted_walls_s": [round(w, 4) for w in fault_walls],
        "tokens_bit_identical": not mismatched,
        "mismatched_uids": mismatched,
        "poisoned_failed_uids": poisoned,
        "expected_poisoned": n_nan,
        "fleet_events": fleet_events,
        "gates": gates,
        "clean": clean_rep.to_dict(),
        "faulted": fault_rep.to_dict(),
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps({
        k: line[k] for k in (
            "metric", "value", "unit", "vs_baseline", "faults_spec",
            "gates",
        )
    }))
    report_path = args.report or artifact_name("SERVE_RESILIENCE")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[serve-faults] report -> {report_path}", file=sys.stderr)
    if not all(gates.values()):
        print(f"[serve-faults] GATES FAILED: {gates}", file=sys.stderr)
        return 1
    return 0


def _run_overload(args) -> int:
    """Overload-survival chaos benchmark: a tenant-classed fleet driven
    past capacity by a best-effort burst (``serve/traffic.py`` +
    ``utils/faults.py`` ``burst``), measured against an ample-capacity
    fault-free twin of the SAME schedule — the ``OVERLOAD_*.json``
    artifact.  Gates (return code 1 on violation):

    - **premium isolated**: premium TTFT/TPOT p99 stay within the
      ``--overload-premium-*-limit`` bounds while best-effort visibly
      degrades (its TTFT p99 is no better than premium's, or it paid
      sheds/preemptions);
    - **preempted streams bit-identical**: at least one request was
      preempted mid-decode and resumed, and EVERY request that completed
      ok carries exactly the clean run's greedy tokens — lossless
      preemption is not allowed to change output;
    - **zero lost requests**: every scheduled uid reaches a terminal
      state and the router counts no losses (shed is terminal WITH a
      retry hint, never silent loss);
    - **shed only best-effort**: admission-time shedding happened (the
      overload was real) and every shed landed in the best_effort class.

    Both runs serve the byte-identical request set (deterministic
    traffic seeds); the overload run feeds arrivals live through the
    router's ``poll`` source while the clean twin takes them upfront
    with ample slots/pages, so the delta IS the overload machinery.
    """
    import dataclasses as _dc

    import jax

    from distributeddeeplearning_tpu.serve import ReplicaSpec, serve_fleet
    from distributeddeeplearning_tpu.serve.traffic import (
        TenantSpec,
        TrafficGenerator,
        poll_source,
    )
    from distributeddeeplearning_tpu.utils import faults as faults_mod

    dims = dict(num_layers=4, d_model=256, num_heads=8, d_ff=1024,
                vocab_size=8193)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    smoke = args.steps_cap is not None
    duration_s = args.overload_duration_s
    ttft_limit = args.overload_premium_ttft_limit
    tpot_limit = args.overload_premium_tpot_limit
    if smoke:
        # CI smoke: shorter schedule, looser premium bounds (a throttled
        # shared host doubles tails that have nothing to do with
        # isolation); the structural gates stay exactly as strict
        duration_s = min(duration_s, 4.0)
        ttft_limit *= 2.0
        tpot_limit *= 2.0
    new_tokens = args.overload_new_tokens
    max_prompt = 16
    max_seq = max_prompt + new_tokens

    tenants = (
        TenantSpec(name="premium", priority="premium", rate_rps=1.5,
                   arrival="poisson", prompt_min=2, prompt_max=max_prompt),
        TenantSpec(name="standard", priority="standard", rate_rps=1.0,
                   arrival="poisson", prompt_min=2, prompt_max=max_prompt),
        TenantSpec(name="best_effort", priority="best_effort", rate_rps=1.0,
                   arrival="poisson", prompt_min=2, prompt_max=max_prompt),
    )
    gen = TrafficGenerator(tenants, vocab_size=dims["vocab_size"], seed=0)
    # the chaos spec CREATES the overload: schedule build consumes the
    # burst fault and splices the extra best-effort arrivals in
    plan = faults_mod.install_plan(args.overload_burst)
    try:
        schedule = gen.schedule(duration_s)
        burst_fired = sum(1 for ev in plan.events if ev.kind == "burst")
    finally:
        faults_mod.reset()
    if burst_fired == 0:
        print(
            f"[overload] burst spec {args.overload_burst!r} never fired "
            "— no overload to survive (tenant name must match a "
            "TenantSpec)", file=sys.stderr,
        )
        return 1
    requests = [tr.request for tr in schedule]
    by_tenant: dict = {}
    for r in requests:
        by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1

    # scarce capacity BY DESIGN: 4 pages per sequence, 11 pages per
    # replica — three slots but pages for ~2.5 concurrent sequences, so
    # admission hits page pressure with a free slot (the preempt/shed
    # ladder) and not just slot pressure
    overload_spec = ReplicaSpec(
        model=dict(max_len=max_seq, **dims),
        seed=0,
        num_heads=dims["num_heads"],
        batch_slots=3,
        max_seq=max_seq,
        kv_layout="paged",
        page_size=8,
        num_pages=args.overload_kv_pages,
        prefill_chunk=8,
        temperature=0.0,  # greedy: the bit-identical gate needs it
        max_new_tokens=new_tokens,
        priority_classes=("premium", "standard", "best_effort"),
        shed_policy="shed",
        preempt_budget=args.overload_preempt_budget,
    )
    clean_spec = _dc.replace(
        overload_spec, batch_slots=4, num_pages=None,
        shed_policy="block",
    )

    print(
        f"[overload] clean twin: 1 replica, ample capacity, "
        f"{len(requests)} requests {by_tenant}", file=sys.stderr,
    )
    clean_res, clean_rep = serve_fleet(
        clean_spec, requests, replicas=1, max_restarts=0,
    )
    if clean_rep.completed_ok != len(requests):
        print(
            f"[overload] clean twin degraded ({clean_rep.finish_reasons})"
            " — no reference to diff the preempted streams against",
            file=sys.stderr,
        )
        return 1
    clean_tokens = {r.uid: list(r.tokens) for r in clean_res}

    print(
        f"[overload] overload fleet: {args.serve_replicas} replicas, "
        f"{overload_spec.batch_slots} slots x {overload_spec.num_pages} "
        f"pages, burst {args.overload_burst!r}, "
        f"{duration_s}s schedule @ x{args.overload_speedup}",
        file=sys.stderr,
    )
    results, rep = serve_fleet(
        overload_spec, [],
        replicas=args.serve_replicas,
        max_restarts=1,
        max_redeliveries=args.overload_max_redeliveries,
        poll=poll_source(schedule, speedup=args.overload_speedup),
    )

    sub_uids = {r.uid for r in requests}
    got_uids = {r.uid for r in results}
    ok_reasons = ("eos", "length")
    mismatched = [
        r.uid for r in results
        if r.finish_reason in ok_reasons
        and list(r.tokens) != clean_tokens[r.uid]
    ]
    resumed = [
        r.uid for r in results
        if r.preemptions > 0 and r.finish_reason in ok_reasons
    ]
    per_class = rep.per_class
    shed_by_class = {
        cls: blk.get("shed", 0) for cls, blk in per_class.items()
    }
    shed_count = sum(shed_by_class.values())
    preemptions = sum(
        blk.get("preemptions", 0) for blk in per_class.values()
    )
    lat = rep.fleet_latency_per_class
    inf = float("inf")

    def p99(cls, block):
        v = lat.get(cls, {}).get(block, {}).get("p99")
        return float(v) if v is not None else inf

    premium_ttft = p99("premium", "ttft_s")
    premium_tpot = p99("premium", "tpot_s")
    be_ttft = p99("best_effort", "ttft_s")
    be_blk = per_class.get("best_effort", {})
    be_suffered = (
        be_ttft >= premium_ttft
        or be_blk.get("shed", 0) > 0
        or be_blk.get("preemptions", 0) > 0
    )
    gates = {
        "premium_isolated": (
            premium_ttft <= ttft_limit
            and premium_tpot <= tpot_limit
            and be_suffered
        ),
        "preempted_resume_bit_identical": (
            len(resumed) > 0 and not mismatched
        ),
        "zero_lost_requests": (
            rep.lost_requests == 0 and got_uids == sub_uids
        ),
        "shed_only_best_effort": (
            shed_count > 0
            and all(
                n == 0 for cls, n in shed_by_class.items()
                if cls != "best_effort"
            )
        ),
    }
    line = {
        "metric": "overload_premium_ttft_p99_s",
        "value": round(premium_ttft, 4),
        "unit": "s",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "faults_spec": args.overload_burst,
        "replicas": args.serve_replicas,
        "requests": len(requests),
        "requests_by_tenant": by_tenant,
        "duration_s": duration_s,
        "speedup": args.overload_speedup,
        "smoke": smoke,
        "max_new_tokens": new_tokens,
        "model_dims": dims,
        "batch_slots": overload_spec.batch_slots,
        "kv_pages": overload_spec.num_pages,
        "preempt_budget": args.overload_preempt_budget,
        "max_redeliveries": args.overload_max_redeliveries,
        # the tracked tail latencies, FLAT at top level by contract
        # (obs/history extracts leaves through dicts only)
        "premium_ttft_p99_s": round(premium_ttft, 4),
        "premium_tpot_p99_s": round(premium_tpot, 4),
        "best_effort_ttft_p99_s": (
            round(be_ttft, 4) if be_ttft != inf else None
        ),
        "premium_ttft_limit_s": ttft_limit,
        "premium_tpot_limit_s": tpot_limit,
        "shed_count": shed_count,
        "shed_by_class": shed_by_class,
        "preemptions": preemptions,
        "per_class": per_class,
        "resumed_streams": sorted(resumed),
        "mismatched_uids": mismatched,
        "gates": gates,
        "clean": clean_rep.to_dict(),
        "fleet_report": rep.to_dict(),
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps({
        k: line[k] for k in (
            "metric", "value", "unit", "shed_count", "preemptions",
            "gates",
        )
    }))
    report_path = args.report or artifact_name("OVERLOAD")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[overload] report -> {report_path}", file=sys.stderr)
    if not all(gates.values()):
        print(f"[overload] GATES FAILED: {gates}", file=sys.stderr)
        return 1
    return 0


def _run_tier(args) -> int:
    """Host-memory KV tier benchmark (``serve/kv_tier.py``) — the
    ``TIER_*.json`` artifact.  Three phases, gates (return code 1 on
    violation):

    - **bit-identical restore**: greedy streams over spilled-then-
      restored prefix pages must equal the never-spilled run exactly —
      paged f32, paged int8 (values AND scale leaves move), and the
      paged f32 run cross-checked against the dense layout.  Mid-chunk
      prefix offsets included (prompt lengths straddle page and chunk
      boundaries);
    - **oversubscription**: ``--tier-sessions`` distinct sessions, each
      re-querying its own multi-page prefix over ``--tier-rounds``
      rounds, against a page pool 4-10x smaller than the prefix working
      set.  Without the tier, eviction forgets the prefixes and every
      round re-prefills; with it, cold pages demote to host and restore
      on the next hit.  Gates: prefix-hit rate strictly above the
      no-tier baseline, admitted-tokens-per-computed-HBM-byte >= 2x;
    - **fits-in-HBM parity**: identical traffic against an ample pool
      with and without the tier attached — decode tokens/sec must stay
      within 2% (the tier must be free when nothing spills).

    Smoke mode (``--steps-cap``) shrinks sessions/rounds and loosens
    only the timing gate; the structural gates stay exactly as strict.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedInferenceEngine,
        Request,
        data_parallel_engine,
    )

    dims = dict(num_layers=4, d_model=256, num_heads=8, d_ff=1024,
                vocab_size=8193)
    if args.small:
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
    smoke = args.steps_cap is not None
    sessions = args.tier_sessions
    rounds = args.tier_rounds
    repeats = 3
    decode_floor = 0.98
    if smoke:
        # CI smoke: smaller session set and one timing repeat with a
        # looser floor (shared-host CPU jitter); the structural gates —
        # bit-identity, hit rate, tokens/HBM-byte — stay exactly strict
        sessions = min(sessions, 12)
        rounds = min(rounds, 2)
        repeats = 1
        decode_floor = 0.90
    page_size = 8
    prefill_chunk = 8
    prefix_pages = 4
    prefix_len = prefix_pages * page_size
    new_tokens = 4
    # one token past the last full prefix page: the walk hits all
    # prefix_pages pages, the final token always runs through prefill
    prompt_len = prefix_len + 1
    req_pages = -(-(prompt_len + new_tokens) // page_size)
    fits_tokens = 16  # phase-3 decode budget: long enough to time
    max_seq = prompt_len + fits_tokens + page_size
    batch_slots = 2
    # scarce BY DESIGN: pages for barely two concurrent sequences, so
    # the session working set oversubscribes the pool by sessions/3x
    num_pages = batch_slots * req_pages + 1
    oversub = sessions * prefix_pages / num_pages
    host_pages = args.host_pages
    if host_pages is None:
        # ample host: the whole prefix working set fits (the hit-rate
        # gate measures the tier, not host-pool churn)
        host_pages = sessions * prefix_pages + 4
    vocab = dims["vocab_size"]
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)

    def paged(cache_dtype=None, tiered=False, pages=num_pages, slots=2):
        return PagedInferenceEngine(
            params,
            num_heads=dims["num_heads"],
            batch_slots=slots,
            max_seq=max_seq,
            page_size=page_size,
            num_pages=pages,
            prefill_chunk=prefill_chunk,
            temperature=0.0,
            cache_dtype=cache_dtype,
            rng=jax.random.key(1),
            host_pages=host_pages if tiered else 0,
            tier_policy=args.tier_policy,
        )

    def run(engine, requests, tokens=new_tokens):
        return ContinuousBatchingScheduler(
            engine, max_new_tokens=tokens
        ).run([Request(uid=u, prompt=list(p)) for u, p in requests])

    def toks(results):
        return {r.uid: list(r.tokens) for r in results}

    # ---- phase 1: bit-identical spill/restore round trips ----
    # mixed lengths over one shared 2-page prefix: 19 and 27 end
    # mid-chunk AND mid-page, 33 ends one past a page boundary
    rng = np.random.default_rng(7)
    base = rng.integers(1, vocab, 16).tolist()
    bit_reqs = [
        (f"bit{i}", base + rng.integers(1, vocab, n - 16).tolist())
        for i, n in enumerate((19, 27, 33))
    ]
    bit_identical = {}
    ref_f32 = None
    for name, cache_dtype in (("paged_f32", None), ("paged_int8", jnp.int8)):
        eng = paged(cache_dtype, tiered=False, pages=24, slots=2)
        never, _ = run(eng, bit_reqs)
        never = toks(never)
        if cache_dtype is None:
            ref_f32 = never
        eng_t = paged(cache_dtype, tiered=True, pages=24, slots=2)
        seeded, _ = run(eng_t, bit_reqs)
        spilled = eng_t.spill_cold_pages(10**6)
        restored_run, _ = run(eng_t, bit_reqs)
        eng_t.allocator.check()
        eng_t.tier.check()
        bit_identical[name] = (
            toks(seeded) == never
            and toks(restored_run) == never
            and spilled > 0
            and eng_t.tier.restored_pages > 0
        )
        print(
            f"[tier] bit-identity {name}: spilled {spilled}, restored "
            f"{eng_t.tier.restored_pages}, "
            f"{'OK' if bit_identical[name] else 'MISMATCH'}",
            file=sys.stderr,
        )
    dense_eng, _ = data_parallel_engine(
        params,
        num_heads=dims["num_heads"],
        batch_slots=2,
        max_seq=max_seq,
        prefill_attention="dense",
        temperature=0.0,
        rng=jax.random.key(1),
    )
    dense_res, _ = run(dense_eng, bit_reqs)
    bit_identical["paged_f32_vs_dense"] = toks(dense_res) == ref_f32

    # ---- phase 2: session oversubscription, tier vs no-tier ----
    prefixes = [
        rng.integers(1, vocab, prefix_len).tolist() for _ in range(sessions)
    ]

    def round_requests(r):
        # each session re-queries its prefix with a fresh final token —
        # the full prefix pages repeat across rounds, the tail never
        # registers (it stays a partial page)
        return [
            (f"s{s}r{r}", prefixes[s] + [1 + (7 * s + 13 * r) % (vocab - 2)])
            for s in range(sessions)
        ]

    def oversub_run(tiered):
        eng = paged(None, tiered=tiered)
        sched = ContinuousBatchingScheduler(eng, max_new_tokens=new_tokens)
        seed_reqs = [
            Request(uid=u, prompt=list(p)) for u, p in round_requests(0)
        ]
        sched.run(seed_reqs)
        eng.reset_stats()
        generated = 0
        spilled = restored = 0
        for r in range(1, rounds + 1):
            reqs = [
                Request(uid=u, prompt=list(p)) for u, p in round_requests(r)
            ]
            _, rep = sched.run(reqs)
            generated += rep.generated_tokens
            spilled, restored = rep.tier_spilled_pages, rep.tier_restored_pages
        eng.allocator.check()
        if eng.tier is not None:
            eng.tier.check()
        computed = (eng.prompt_tokens_seen - eng.prefix_hit_tokens) + generated
        bytes_computed = computed * eng.page_bytes_each / page_size
        admitted = eng.prompt_tokens_seen + generated
        return {
            "hit_rate": round(eng.prefix_hit_rate(), 4),
            "hit_tokens_host": eng.prefix_hit_tokens_host,
            "admitted_tokens": admitted,
            "computed_tokens": computed,
            "tok_per_hbm_byte": admitted / bytes_computed,
            "spilled": spilled,
            "restored": restored,
        }

    print(
        f"[tier] oversubscription: {sessions} sessions x {prefix_pages} "
        f"prefix pages over {num_pages} pool pages ({oversub:.1f}x), "
        f"{rounds} measured round(s), host pool {host_pages} pages",
        file=sys.stderr,
    )
    no_tier = oversub_run(tiered=False)
    tiered = oversub_run(tiered=True)
    byte_ratio = (
        tiered["tok_per_hbm_byte"] / no_tier["tok_per_hbm_byte"]
        if no_tier["tok_per_hbm_byte"] else float("inf")
    )

    # ---- phase 3: decode-throughput parity when the set fits ----
    fits_reqs = [
        (f"f{i}", rng.integers(1, vocab, prompt_len).tolist())
        for i in range(8)
    ]

    # ample pool: every request's pages PLUS its registered prefix pages
    # stay resident across repeats — nothing ever evicts, so an observed
    # spill means the tier leaked work onto the no-pressure path
    fits_pages = len(fits_reqs) * -(-(prompt_len + fits_tokens)
                                    // page_size) + 4
    fits_engines = {
        name: paged(None, tiered=flag, pages=fits_pages, slots=4)
        for name, flag in (("no_tier", False), ("tier", True))
    }
    fits_best = {"no_tier": 0.0, "tier": 0.0}
    for eng in fits_engines.values():  # warmup: compiles out of the timing
        run(eng, fits_reqs, tokens=fits_tokens)
    # INTERLEAVED repeats, best-of each: a host-load swing during one
    # engine's block would otherwise read as tier overhead (or mask it)
    for _ in range(repeats):
        for name, eng in fits_engines.items():
            _, rep = run(eng, fits_reqs, tokens=fits_tokens)
            assert rep.tier_spilled_pages == 0, (
                "working set fits in HBM yet the tier spilled — the "
                "parity phase is measuring spill traffic, not overhead"
            )
            fits_best[name] = max(fits_best[name], rep.decode_tokens_per_sec)
    fits_base, fits_tier = fits_best["no_tier"], fits_best["tier"]
    decode_ratio = fits_tier / fits_base if fits_base else 0.0

    gates = {
        "bit_identical": all(bit_identical.values()),
        "prefix_hit_rate": tiered["hit_rate"] > no_tier["hit_rate"],
        "tokens_per_hbm_byte": byte_ratio >= 2.0,
        "decode_tokens_per_sec": decode_ratio >= decode_floor,
    }
    line = {
        "metric": "kv_tier_tokens_per_hbm_byte_ratio",
        "value": round(byte_ratio, 2),
        "unit": "x",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "smoke": smoke,
        "model_dims": dims,
        "dims": dims,
        "page_size": page_size,
        "prefill_chunk": prefill_chunk,
        "batch_slots": batch_slots,
        "num_pages": num_pages,
        "host_pages": host_pages,
        "tier_policy": args.tier_policy,
        "sessions": sessions,
        "rounds": rounds,
        "oversubscription": round(oversub, 2),
        "max_new_tokens": new_tokens,
        "bit_identical": bit_identical,
        # the tracked leaves, FLAT at top level by contract and
        # tier_-prefixed so they never collide with the global
        # prefix_hit_rate / decode_tokens_per_sec budgets
        "tier_prefix_hit_rate": tiered["hit_rate"],
        "tier_prefix_hit_rate_no_tier": no_tier["hit_rate"],
        "tier_tokens_per_hbm_byte_ratio": round(byte_ratio, 2),
        "tier_decode_tokens_per_sec_ratio": round(decode_ratio, 4),
        "configs": {
            "oversubscribed_tier": tiered,
            "oversubscribed_no_tier": no_tier,
            "fits_in_hbm": {
                "decode_tok_s_no_tier": round(fits_base, 2),
                "decode_tok_s_tier": round(fits_tier, 2),
                "repeats": repeats,
            },
        },
        "gates": gates,
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps({
        k: line[k] for k in (
            "metric", "value", "unit", "tier_prefix_hit_rate",
            "tier_prefix_hit_rate_no_tier",
            "tier_decode_tokens_per_sec_ratio", "gates",
        )
    }))
    report_path = args.report or artifact_name("TIER")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[tier] report -> {report_path}", file=sys.stderr)
    if not all(gates.values()):
        print(f"[tier] GATES FAILED: {gates}", file=sys.stderr)
        return 1
    return 0


def _run_ckpt_faults(args) -> int:
    """Durable-state chaos benchmark (``train/checkpoint.py`` manifests +
    verified restore + live fleet weight reload) — the
    ``CKPT_DURABLE_*.json`` artifact.  Gates (return code 1 on violation):

    - **resume exact / zero bricked**: with ``ckpt_corrupt`` injected on
      the LATEST generation of a real training run, a fresh restore lands
      on the newest VERIFIED generation at the exact step, and the
      Trainer resumes from there to completion — no exception, no
      restart-loop, one generation of progress lost;
    - **every corruption mode recovered**: flip / truncate / unlink /
      manifest plus a torn writer (``ckpt_torn``) each leave the store
      restorable from the previous generation;
    - **reload bit-identical**: a 2-replica fleet serves a batch, live-
      reloads a different checkpoint's weights
      (``FleetRouter.reload``), serves a second batch — whose greedy
      tokens must be BIT-IDENTICAL to a fresh engine started from that
      checkpoint;
    - **verify overhead**: manifest build + verification wall under
      ``--ckpt-verify-overhead-limit`` %% of the save wall.
    """
    import dataclasses as _dc
    import shutil
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributeddeeplearning_tpu.data.synthetic import SyntheticDataset
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.obs.registry import get_registry
    from distributeddeeplearning_tpu.obs import trace as trace_mod
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedInferenceEngine,
        ReplicaSpec,
        Request,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.serve.fleet import FleetRouter
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer
    from distributeddeeplearning_tpu.train.loop import Trainer, TrainerConfig
    from distributeddeeplearning_tpu.train.state import (
        create_train_state,
        sgd_momentum,
    )
    from distributeddeeplearning_tpu.train.step import build_train_step
    from distributeddeeplearning_tpu.utils import faults as faults_mod
    from distributeddeeplearning_tpu.utils.hardware import probe_devices
    from distributeddeeplearning_tpu.utils.virtual_pod import (
        cpu_platform_pinned,
    )

    if not cpu_platform_pinned() and probe_devices()["platform"] != "cpu":
        # this mode trains and serves in THIS process and runs a fleet of
        # workers beside it: on a chip host the parent would hold the
        # chips its workers need.  Its gates are counts (bit-identity,
        # verified restores), which the CPU gives just as well.
        print(
            "[bench] --ckpt-faults drives JAX in the parent next to its "
            "fleet workers, so it does not run on a chip host: run it "
            "with JAX_PLATFORMS=cpu", file=sys.stderr,
        )
        return 2

    work_dir = tempfile.mkdtemp(prefix="ddlt-ckpt-faults-")
    reg = get_registry()

    @_dc.dataclass
    class _MiniState:
        """Minimal TrainState stand-in for checkpoint-layer phases that
        need no optimizer (the Checkpointer only touches these fields)."""

        step: object
        params: object
        opt_state: object
        batch_stats: object

        def replace(self, **kw):
            return _dc.replace(self, **kw)

    # ---- phase A: verified saves + corrupt-latest resume (real Trainer)
    img, ncls, batch = (24, 24, 3), 7, 16
    mesh = create_mesh(MeshSpec())
    if args.small:
        # CI smoke: a dense head instead of resnet18 — the durability
        # machinery under test is model-agnostic, and the smoke runs as
        # a subprocess NEXT TO a pytest-held jax session, where two
        # resnet compiles have been observed to OOM-crash the box
        import flax.linen as nn

        class _TinyBenchModel(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                return nn.Dense(ncls)(x.reshape((x.shape[0], -1)))

        model = _TinyBenchModel()
    else:
        model = get_model("resnet18", num_classes=ncls, dtype=jnp.float32)
    tx = sgd_momentum(optax.constant_schedule(0.05))

    def mk_state():
        return create_train_state(jax.random.key(0), model, (8, *img), tx)

    train_step = build_train_step(mesh, mk_state(), compute_dtype=jnp.float32)
    ds = SyntheticDataset(length=4096, image_shape=img, num_classes=ncls)
    batches = list(ds.batches(batch))

    def factory(start_step: int):
        def gen():
            i = start_step
            while True:
                yield batches[i % len(batches)]
                i += 1

        return gen()

    steps_per_epoch, epochs, every = 4, 2, 2
    total_steps = steps_per_epoch * epochs
    ckpt_dir = f"{work_dir}/train"
    cfg = TrainerConfig(
        epochs=epochs, steps_per_epoch=steps_per_epoch,
        global_batch_size=batch, log_every=100,
        checkpoint_dir=ckpt_dir, checkpoint_every_steps=every,
        prefetch=0,
    )
    n_generations = total_steps // every
    print(
        f"[ckpt-faults] training {total_steps} steps, checkpoint every "
        f"{every} -> {n_generations} generations, faults: "
        f"{args.ckpt_faults_spec}", file=sys.stderr,
    )
    faults_mod.install_plan(args.ckpt_faults_spec)
    tracer = trace_mod.set_tracer(
        trace_mod.Tracer(enabled=True, annotate=False)
    )
    try:
        Trainer(mesh, train_step, config=cfg).fit(mk_state(), factory)
    finally:
        trace_mod.set_tracer(trace_mod.Tracer(enabled=False))
    faults_injected = faults_mod.get_plan().report()
    faults_mod.install_plan("")  # the resume must run fault-free

    # the training Trainer's checkpointer is out of scope after fit; a
    # fresh one measures the resume.  Expected: the corrupt LATEST
    # generation (step 8) fails verification, the walk falls back to the
    # newest verified one (step 6) — exactly one generation of progress.
    # The fallback must be OBSERVABLE: obs event + counter + a flight-
    # recorder dump naming the failed generation (tracer enabled around
    # exactly this restore so the artifact carries the evidence).
    from distributeddeeplearning_tpu.obs.recorder import get_recorder

    expected_step = total_steps - every
    verify_failures_before = reg.counter("ckpt.verify_failures").value
    get_recorder().drain_dumps()
    resume_tracer = trace_mod.set_tracer(
        trace_mod.Tracer(enabled=True, annotate=False)
    )
    try:
        ckpt = Checkpointer(ckpt_dir)
        try:
            state, resumed_step = ckpt.restore(mk_state())
        finally:
            ckpt.close()
    finally:
        trace_mod.set_tracer(trace_mod.Tracer(enabled=False))
    verify_failures = (
        reg.counter("ckpt.verify_failures").value - verify_failures_before
    )
    verify_events = [
        ev for ev in resume_tracer.events
        if ev.get("name") == "ckpt/verify_failed"
    ]
    ckpt_dumps = [
        d for d in get_recorder().drain_dumps()
        if d.get("reason") == "ckpt_verify_failed"
    ]
    resume_exact = (
        resumed_step == expected_step
        and int(np.asarray(state.step)) == expected_step
    )
    print(
        f"[ckpt-faults] corrupt-latest resume: restored step "
        f"{resumed_step} (expected {expected_step}), "
        f"{verify_failures} verification failure(s) recorded",
        file=sys.stderr,
    )
    # ... and the REAL loop trains on from the fallback to completion —
    # the no-brick half of the gate (restore above proved the step)
    bricked = False
    try:
        final_state, _ = Trainer(mesh, train_step, config=cfg).fit(
            mk_state(), factory
        )
        resumed_to_end = int(np.asarray(final_state.step)) == total_steps
    except Exception as exc:  # noqa: BLE001 — a brick IS the failure mode
        print(f"[ckpt-faults] resume run BRICKED: {exc}", file=sys.stderr)
        bricked = True
        resumed_to_end = False

    # ---- phase B: every corruption mode recovers to the previous gen
    tiny = _MiniState(
        step=jnp.int32(0),
        params={"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)},
        opt_state={}, batch_stats={},
    )
    corrupt_modes = {}
    for mode in ("flip", "truncate", "unlink", "manifest", "torn"):
        mdir = f"{work_dir}/mode-{mode}"
        spec_text = (
            "ckpt_torn@2" if mode == "torn"
            else f"ckpt_corrupt@2:mode={mode}"
        )
        faults_mod.install_plan(spec_text)
        c = Checkpointer(mdir)
        try:
            c.save(1, tiny.replace(step=jnp.int32(1)))
            c.save(2, tiny.replace(step=jnp.int32(2)))
            c.wait()
            recovered, fallback_step = False, None
            try:
                _, fallback_step = c.restore(tiny)
                recovered = fallback_step == 1
            except Exception as exc:  # noqa: BLE001 — recovery gate data
                print(
                    f"[ckpt-faults] mode {mode}: restore raised "
                    f"{type(exc).__name__}: {exc}", file=sys.stderr,
                )
        finally:
            c.close()
            faults_mod.install_plan("")
        corrupt_modes[mode] = {
            "spec": spec_text,
            "recovered": bool(recovered),
            "fallback_step": fallback_step,
        }
        print(
            f"[ckpt-faults] mode {mode}: recovered={recovered} "
            f"(fallback step {fallback_step})", file=sys.stderr,
        )

    # ---- phase C: verify overhead vs save wall (fault-free saves of the
    # real train state — the number a production run pays per generation).
    # The denominator is the FULL persist wall of the generations (save
    # dispatches + the drain that lands them); the numerator is the wall
    # the durability layer ADDED to that path — host snapshot + finalize
    # joins — while the checksum CPU work itself overlaps the async write
    # (reported separately as verify_cpu_s).
    over = Checkpointer(f"{work_dir}/overhead", max_to_keep=3)
    try:
        st = mk_state()
        t0 = _time.perf_counter()
        for i in range(1, 5):
            over.save(i, st.replace(step=jnp.int32(i)))
        over.wait()
        persist_wall = _time.perf_counter() - t0
        save_wall = persist_wall
        verify_wall = over.verify_wall_s
        verify_cpu = over.verify_cpu_s
        snapshot_wall = over.snapshot_wall_s
    finally:
        over.close()
    overhead_pct = round(100.0 * verify_wall / max(save_wall, 1e-9), 2)
    print(
        f"[ckpt-faults] verify overhead: {verify_wall * 1e3:.1f}ms added "
        f"to a {save_wall * 1e3:.1f}ms persist wall = {overhead_pct}% "
        f"(checksum CPU overlapped with the write: {verify_cpu * 1e3:.1f}ms; "
        f"donation-safety snapshot memcpy, paid by any correct async "
        f"save: {snapshot_wall * 1e3:.1f}ms)",
        file=sys.stderr,
    )

    # ---- phase D: live weight reload across the fleet, pinned against a
    # fresh engine from the reloaded checkpoint
    dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                vocab_size=257)
    max_seq = 48
    p_old = init_params(jax.random.key(1), max_len=max_seq, **dims)
    p_new = init_params(jax.random.key(2), max_len=max_seq, **dims)
    dir_old, dir_new = f"{work_dir}/w-old", f"{work_dir}/w-new"
    for d, p in ((dir_old, p_old), (dir_new, p_new)):
        c = Checkpointer(d)
        try:
            c.save(1, _MiniState(
                step=jnp.int32(1), params=p, opt_state={}, batch_stats={},
            ))
            c.wait()
        finally:
            c.close()
    spec = ReplicaSpec(
        checkpoint_dir=dir_old,
        num_heads=dims["num_heads"], batch_slots=2, max_seq=max_seq,
        kv_layout="paged", page_size=8, prefill_chunk=8,
        temperature=0.0, max_new_tokens=12,
    )
    batch_a = synthetic_requests(
        6, vocab_size=dims["vocab_size"], max_prompt=10,
        rng=np.random.default_rng(0),
    )
    batch_b = [
        Request(uid=f"post-reload-{i}", prompt=r.prompt)
        for i, r in enumerate(synthetic_requests(
            6, vocab_size=dims["vocab_size"], max_prompt=10,
            rng=np.random.default_rng(1),
        ))
    ]
    replicas = 2
    print(
        f"[ckpt-faults] fleet reload: {replicas} replicas, "
        f"{len(batch_a)}+{len(batch_b)} requests", file=sys.stderr,
    )
    router = FleetRouter(spec, replicas=replicas, faults="")
    _, rep_a = router.serve(batch_a, shutdown=False)
    acks = router.reload(dir_new)
    res_b, rep_b = router.serve(batch_b)
    acks_ok = sum(1 for a in acks.values() if a.get("ok"))
    # the reference: a fresh engine built from the reloaded checkpoint
    ref_ckpt = Checkpointer(dir_new)
    try:
        ref_params, _ = ref_ckpt.restore_params()
    finally:
        ref_ckpt.close()
    ref_engine = PagedInferenceEngine(
        ref_params, num_heads=dims["num_heads"], batch_slots=2,
        max_seq=max_seq, page_size=8, prefill_chunk=8, temperature=0.0,
        rng=jax.random.key(spec.seed),
    )
    ref_res, _ = ContinuousBatchingScheduler(
        ref_engine, max_new_tokens=12,
    ).run([Request(uid=r.uid, prompt=r.prompt) for r in batch_b])
    ref_tokens = {r.uid: list(r.tokens) for r in ref_res}
    mismatched = [
        r.uid for r in res_b
        if r.finish_reason in ("eos", "length")
        and list(r.tokens) != ref_tokens[r.uid]
    ]
    reload_ok = (
        acks_ok == replicas
        and rep_b.completed_ok == len(batch_b)
        and not mismatched
    )
    print(
        f"[ckpt-faults] reload: {acks_ok}/{replicas} acks, "
        f"bit_identical={not mismatched}", file=sys.stderr,
    )

    gates = {
        "resume_exact": bool(resume_exact),
        "zero_bricked": bool(not bricked and resumed_to_end),
        "corrupt_modes_recovered": all(
            m["recovered"] for m in corrupt_modes.values()
        ),
        "reload_bit_identical": bool(reload_ok),
        "verify_overhead_under_limit": (
            overhead_pct < args.ckpt_verify_overhead_limit
        ),
        # the fallback left evidence: a ckpt/verify_failed obs event AND
        # a flight-recorder dump, each naming the corrupt generation
        "fallback_observable": bool(
            any(
                isinstance(ev.get("args"), dict)
                and ev["args"].get("step") == total_steps
                for ev in verify_events
            )
            and any(
                d.get("generation") == total_steps for d in ckpt_dumps
            )
        ),
    }
    line = {
        "metric": "ckpt_durable_verify_overhead_pct",
        "value": overhead_pct,
        "unit": "%",
        "vs_baseline": None,
        "bench_revision": BENCH_REVISION,
        "faults_spec": args.ckpt_faults_spec,
        "faults_injected": faults_injected,
        "resume": {
            "total_steps": total_steps,
            "checkpoint_every_steps": every,
            "corrupt_step": total_steps,
            "expected_step": int(expected_step),
            "resumed_step": int(resumed_step) if resumed_step else -1,
            "exact": bool(resume_exact),
            "resumed_to_end": bool(resumed_to_end),
            "verify_failures_observed": int(verify_failures),
            "verify_failed_events": len(verify_events),
            "failed_generations": sorted({
                ev["args"].get("step") for ev in verify_events
                if isinstance(ev.get("args"), dict)
            }) if verify_events else [],
            "failed_leaf": next(
                (
                    ev["args"].get("leaf") for ev in verify_events
                    if isinstance(ev.get("args"), dict)
                    and ev["args"].get("leaf")
                ),
                None,
            ),
            "flight_recorder_dumps": len(ckpt_dumps),
        },
        "corrupt_modes": corrupt_modes,
        "reload": {
            "replicas": replicas,
            "acks": acks_ok,
            "ack_detail": {str(k): v for k, v in acks.items()},
            "requests": len(batch_b),
            "completed_ok": rep_b.completed_ok,
            "bit_identical": not mismatched,
            "mismatched_uids": mismatched,
            "fleet_reloads": rep_b.reloads,
            "pre_reload_completed_ok": rep_a.completed_ok,
        },
        "verify_overhead": {
            "save_wall_s": round(save_wall, 4),
            "verify_wall_s": round(verify_wall, 4),
            "verify_cpu_overlapped_s": round(verify_cpu, 4),
            # the donation-safety memcpy: a CORRECT async save with
            # donated states pays this with or without manifests (the
            # background write would otherwise alias the donated buffer)
            "snapshot_wall_s": round(snapshot_wall, 4),
            "pct": overhead_pct,
            "limit_pct": args.ckpt_verify_overhead_limit,
        },
        "gates": gates,
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    shutil.rmtree(work_dir, ignore_errors=True)
    print(json.dumps({
        k: line[k] for k in ("metric", "value", "unit", "vs_baseline",
                             "faults_spec", "gates")
    }))
    report_path = args.report or artifact_name("CKPT_DURABLE")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[ckpt-faults] report -> {report_path}", file=sys.stderr)
    if not all(gates.values()):
        print(f"[ckpt-faults] GATES FAILED: {gates}", file=sys.stderr)
        return 1
    return 0


def _run_comms(args) -> int:
    """Gradient-communication benchmark: the explicit comm_overlap schedule
    (``parallel/comms.py`` — bucketed reduce-scatter in the accumulation
    scan, optional ZeRO weight-update sharding, optional bf16 compressed
    wire) against the implicit-GSPMD baseline ON THE SAME MODEL.

    Emits the ``COMMS_r{NN}.json`` artifact (``artifact_name("COMMS")`` — the
    current ``BENCH_REVISION``): per-mode step time, per-step
    bytes-on-wire (both the analytic ring model and the compiled-HLO
    collective signature — the platform-independent, quotable half), and
    overlap efficiency = exposed-comms / total-comms, where exposed is the
    comm time the overlapped schedule fails to hide (its step time minus a
    collective-elided ``comm_skip`` build of the same program) and total is
    the implicit baseline's serialized comm time measured the same way.
    On a virtual CPU pod wall-clock overlap is an artifact of host-core
    contention (flagged via ``platform``/``virtual_pod``); the HLO byte
    table is the part that transfers to hardware.
    """
    import time as _time

    import jax

    from distributeddeeplearning_tpu.train.state import create_train_state
    from distributeddeeplearning_tpu.train.step import build_train_step
    from distributeddeeplearning_tpu.utils.virtual_pod import is_reexec_child

    # the comparison needs real data-parallel shards
    rc = _ensure_devices(2, "--comms")
    if rc is not None:
        return rc

    import jax.numpy as jnp

    step0, state0, batch, n_dev, (mesh, model, tx, init_shape, init_kw) = (
        _build_bench(args)
    )
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    accum = args.accum_steps
    smoke = args.steps_cap is not None
    warmup_steps = 1 if smoke else 3
    timed_steps = args.steps_cap if smoke else 10

    def fresh_state(seed):
        return create_train_state(
            jax.random.key(seed), model, init_shape, tx, **init_kw
        )

    def build(seed, **comm_kwargs):
        state = fresh_state(seed)
        step = build_train_step(
            mesh, state, compute_dtype=dtype, accum_steps=accum,
            **comm_kwargs,
        )
        if comm_kwargs.get("comm_overlap"):
            state = step.prepare_state(state)
        return step, state

    def measure(step, state):
        """(seconds/step, collective HLO stats, wire-model dict|None)."""
        compiled = step.lower(state, batch).compile()
        coll = _collective_stats(compiled.as_text())
        metrics = None
        for _ in range(warmup_steps):
            state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        t0 = _time.perf_counter()
        for _ in range(timed_steps):
            state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        per_step = (_time.perf_counter() - t0) / timed_steps
        wire = step.wire_bytes() if hasattr(step, "wire_bytes") else None
        return per_step, coll, wire

    all_modes = {
        "implicit": {},
        "overlap": dict(comm_overlap=True, bucket_mb=args.bucket_mb),
        "overlap_wus": dict(
            comm_overlap=True, bucket_mb=args.bucket_mb,
            weight_update_sharding=True,
        ),
        "overlap_bf16": dict(
            comm_overlap=True, bucket_mb=args.bucket_mb, comm_dtype="bf16",
        ),
    }
    selected = [m.strip() for m in args.comms_modes.split(",") if m.strip()]
    unknown = [m for m in selected if m not in all_modes]
    if unknown or not {"implicit", "overlap"} <= set(selected):
        print(
            f"[comms] --comms-modes must include implicit,overlap and only "
            f"draw from {sorted(all_modes)} (got {selected})",
            file=sys.stderr,
        )
        return 2
    modes = {name: all_modes[name] for name in all_modes if name in selected}
    del step0, state0  # rebuilt below: every mode (implicit included) must
    # compile with the SAME accum_steps or the step-time ratio would
    # compare different microbatching schedules
    rows = {}
    for i, (name, kwargs) in enumerate(modes.items()):
        step, state = build(i + 1, **kwargs)
        per_step, coll, wire = measure(step, state)
        rows[name] = {
            "step_time_s": round(per_step, 5),
            "collectives_per_step": coll,
            "hlo_collective_bytes_per_step": sum(
                s["bytes"] for s in coll.values()
            ),
        }
        if wire:
            rows[name]["ring_wire_bytes_per_step_per_device"] = wire
        print(
            f"[comms] {name}: {per_step * 1e3:.1f} ms/step, "
            f"{rows[name]['hlo_collective_bytes_per_step']} HLO collective "
            "bytes/step",
            file=sys.stderr,
        )

    # collective-elided twin of the overlap program: its step time is the
    # pure compute cost, the subtrahend of both comm-time estimates
    nc_step, nc_state = build(
        9, comm_overlap=True, bucket_mb=args.bucket_mb, comm_skip=True
    )
    t_compute, _, _ = measure(nc_step, nc_state)
    t_base = rows["implicit"]["step_time_s"]
    eps = 1e-9
    for name in rows:
        if name == "implicit":
            continue
        # clamped at eps so the documented (0, 1] range holds even when
        # CPU-contention noise makes the compute-only twin measure slower
        # than the mode itself
        exposed = max(rows[name]["step_time_s"] - t_compute, eps)
        # total serialized comm time, from the implicit baseline; clamped
        # to >= exposed so the ratio stays in (0, 1] when CPU-contention
        # noise makes the compute-only twin slower than the whole GSPMD
        # program (ratio 1.0 then reads "no overlap demonstrated" — the
        # honest verdict for a virtual pod)
        total = max(t_base - t_compute, exposed, eps)
        rows[name]["exposed_comms_s_per_step"] = round(exposed, 5)
        rows[name]["total_comms_s_per_step"] = round(total, 5)
        rows[name]["overlap_efficiency"] = round(exposed / total, 4)

    # the compressed-wire claim comes from the ring model (analytic, so it
    # never depends on which modes ran): XLA backends without native bf16
    # reduction (CPU) promote the collective to f32 in HLO, and in-scan
    # reduce-scatters appear once in program text but execute accum_steps
    # times — the analytic table prices the actual wire schedule
    from distributeddeeplearning_tpu.parallel import comms as comms_mod

    layout = nc_step.layout
    rs_f32 = comms_mod.ring_wire_bytes(
        layout, comm_dtype=None, accum_steps=accum
    )["reduce_scatter_bytes"]
    rs_bf16 = comms_mod.ring_wire_bytes(
        layout, comm_dtype=jnp.bfloat16, accum_steps=accum
    )["reduce_scatter_bytes"]
    line = {
        "metric": f"{args.model}_comm_overlap_vs_implicit_step_time_ratio",
        "value": round(rows["overlap"]["step_time_s"] / max(t_base, eps), 4),
        "unit": "x",
        "vs_baseline": None,
        "modes": rows,
        "compute_only_step_time_s": round(t_compute, 5),
        "compressed_vs_f32_wire_ratio": (
            round(rs_bf16 / rs_f32, 4) if rs_f32 else None
        ),
        "hlo_caveat": (
            "collectives_per_step sums program TEXT: in-scan reduce-"
            "scatters execute accum_steps times per step, and backends "
            "without native bf16 reduction (CPU) promote compressed "
            "collectives to f32 in HLO — ring_wire_bytes_per_step_per_"
            "device prices the schedule as specified"
        ),
        "bucket_mb": args.bucket_mb,
        "accum_steps": accum,
        "num_devices": n_dev,
        "batch_size_per_chip": args.batch_size,
        "wall_clock_caveat": (
            "virtual-pod CPU wall clock measures host-core contention, not "
            "ICI overlap; the HLO collective table is the portable half"
        ) if is_reexec_child() or _is_virtual_pod() else None,
        "platform": jax.default_backend(),
        "virtual_pod": _is_virtual_pod(),
    }
    print(json.dumps(line))
    report_path = args.report or artifact_name("COMMS")
    with open(report_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    print(f"[comms] report -> {report_path}", file=sys.stderr)
    return 0


def _collective_stats(hlo_text: str):
    """Compiled-HLO collective signature — the implementation moved to
    ``parallel/comms.collective_stats`` so `ddlt lint`'s program audit
    shares the exact parser the bench artifacts quote."""
    from distributeddeeplearning_tpu.parallel.comms import collective_stats

    return collective_stats(hlo_text)


def _run_scaling(args) -> int:
    """Collective-signature sweep over increasing mesh sizes.

    The QUOTABLE scaling evidence from a single-host box is what the
    compiled program does, not how fast faked CPU devices run it: per mesh
    size this compiles the full train step and reports the collective op
    counts and bytes moved per step straight from the optimized HLO
    (VERDICT r4 item 7 — the r3/r4 wall-clock "efficiency" number measured
    host-core contention and invited mis-quotation).  Wall-clock totals are
    still collected but only as an explicitly-labeled debug column.
    """
    from distributeddeeplearning_tpu.utils.virtual_pod import is_reexec_child

    sizes = sorted({int(x) for x in args.devices.split(",")})
    if sizes[0] != 1:
        # The 1-chip point anchors both tables: zero collectives, and the
        # wall-clock debug ratio is defined against it.
        print("[scaling] adding the 1-chip baseline point", file=sys.stderr)
        sizes.insert(0, 1)

    import jax

    rc = _ensure_devices(max(sizes), "--devices")
    if rc is not None:
        return rc

    from distributeddeeplearning_tpu.train.benchmark import run_benchmark

    totals = {}
    collectives = {}
    for n in sizes:
        trace = (
            jax.profiler.trace(f"{args.trace_dir}/devices-{n}")
            if args.trace_dir
            else contextlib.nullcontext()
        )
        step, state, batch, n_dev, _ = _build_bench(
            args, devices=jax.devices()[:n]
        )
        # one AOT compile per mesh size: the HLO text AND the executable the
        # wall-clock debug loop runs (compiling again through the jit cache
        # would double the sweep's dominant cost)
        compiled = step.lower(state, batch).compile()
        collectives[str(n)] = _collective_stats(compiled.as_text())
        with trace:
            result = run_benchmark(
                compiled,
                state,
                batch,
                model_name=args.model,
                batch_size_per_chip=args.batch_size,
                num_devices=n_dev,
                num_warmup_batches=args.num_warmup,
                num_iters=args.num_iters,
                num_batches_per_iter=args.num_batches_per_iter,
                log=lambda msg, n=n: print(f"[{n} dev] {msg}", file=sys.stderr),
            )
        totals[n] = result.img_sec_total

    n_max = sizes[-1]
    bytes_max = sum(s["bytes"] for s in collectives[str(n_max)].values())
    per_chip_1 = totals[1]
    print(
        json.dumps(
            {
                "metric": (
                    f"{args.model}_collective_bytes_per_step_{n_max}chip"
                ),
                "value": bytes_max,
                "unit": "bytes",
                "vs_baseline": None,
                # per-mesh-size compiled-HLO collective signature: op ->
                # {count, bytes}.  Platform-independent — the same program
                # XLA lays onto ICI on a real pod.
                "collectives_per_step": collectives,
                # wall clock on this host is DEBUG ONLY: all virtual
                # devices share one CPU core, so the ratio reads back core
                # contention, not ICI scaling.
                "debug_wall_clock": {
                    "img_sec_total": {
                        str(n): round(v, 1) for n, v in totals.items()
                    },
                    "ratio_vs_linear": {
                        str(n): round(totals[n] / (n * per_chip_1), 4)
                        for n in sizes
                    },
                    "platform": jax.default_backend(),
                    "virtual_pod": is_reexec_child(),
                    "caveat": "single-host CPU contention; not an ICI "
                    "measurement",
                },
            }
        )
    )
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=256)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=128,
                        help="sequence length for --model bert-*")
    parser.add_argument("--attention", default="default",
                        choices=("default", "flash"),
                        help="attention primitive for --model bert-*")
    parser.add_argument("--remat", default="none",
                        choices=("none", "full", "dots"),
                        help="encoder-layer rematerialization for bert-*")
    parser.add_argument("--model", default="resnet50")
    parser.add_argument(
        "--loss-chunk", type=int, default=None,
        help="lm only: fuse the head matmul into a chunked CE so the full "
        "[b,s,vocab] f32 logits never materialize (seq-64k memory lever); "
        "must divide seq_len-1",
    )
    parser.add_argument("--num-iters", type=int, default=5)
    parser.add_argument("--num-batches-per-iter", type=int, default=20)
    parser.add_argument("--num-warmup", type=int, default=10)
    parser.add_argument(
        "--small", action="store_true", help="tiny shapes for CI smoke"
    )
    parser.add_argument(
        "--lint", action="store_true",
        help="preflight: run `ddlt lint` (both analyzer layers) and abort "
        "before benchmarking if the tree has open findings — committed "
        "artifacts can then never come from a dirty tree",
    )
    parser.add_argument(
        "--scan-unroll", type=int, default=1,
        help="LM layer-scan unroll factor (removes scan-carry DUS traffic "
        "from the backward at the cost of compile time)",
    )
    parser.add_argument(
        "--fp32", action="store_true", help="disable bf16 compute"
    )
    parser.add_argument(
        "--fit",
        action="store_true",
        help="also measure Trainer.fit throughput over the same step "
        "(device-resident batches) and report fit_vs_harness",
    )
    parser.add_argument(
        "--devices",
        default=None,
        help="comma list of mesh sizes for the scaling-efficiency sweep, "
        "e.g. 1,2,4,8 (forces a virtual CPU pod if too few real chips)",
    )
    parser.add_argument(
        "--trace-dir",
        default=None,
        help="write a jax.profiler trace of the timed run here",
    )
    parser.add_argument(
        "--roofline",
        action="store_true",
        help="trace steady-state steps and emit the HBM-roofline analysis "
        "(GB/step, per-category GB/s, implied ceiling img/s) as the JSON line",
    )
    parser.add_argument(
        "--roofline-steps",
        type=int,
        default=10,
        help="steps to trace for --roofline",
    )
    parser.add_argument(
        "--serve",
        action="store_true",
        help="benchmark the KV-cached serving engine (serve/) under "
        "continuous batching instead of a train step; emits the "
        "SERVE_*.json line (tok/s, TTFT p50/p99, slot occupancy)",
    )
    parser.add_argument(
        "--serve-requests",
        type=int,
        default=12,
        help="synthetic requests for --serve (keep > --batch-slots so "
        "slot release/reuse is exercised)",
    )
    parser.add_argument(
        "--batch-slots",
        type=int,
        default=4,
        help="KV-cache slots (the decode batch) for --serve",
    )
    parser.add_argument(
        "--max-new-tokens",
        type=int,
        default=16,
        help="per-request generation budget for --serve",
    )
    parser.add_argument(
        "--serve-temperature",
        type=float,
        default=0.0,
        help="sampling temperature for --serve (0 = greedy)",
    )
    parser.add_argument(
        "--kv-layout",
        default="dense",
        choices=("dense", "paged", "both"),
        help="KV-cache layout for --serve: dense (per-slot max_seq "
        "reservation), paged (page pool + block tables + chunked "
        "prefill), or both — the paged-vs-dense comparison artifact "
        "(SERVE_PAGED_*.json: bit-exactness gate, HBM bytes per admitted "
        "token, prefix-hit rate on a shared-prefix workload)",
    )
    parser.add_argument(
        "--page-size",
        type=int,
        default=16,
        help="tokens per KV page for --kv-layout paged/both",
    )
    parser.add_argument(
        "--prefill-chunk",
        type=int,
        default=32,
        help="prompt tokens prefilled per interleaved chunk "
        "(--kv-layout paged/both)",
    )
    parser.add_argument(
        "--kv-pages",
        type=int,
        default=None,
        help="page-pool size for --kv-layout paged (default: dense-"
        "capacity parity, batch_slots x ceil(max_seq/page_size))",
    )
    parser.add_argument(
        "--steps-cap",
        type=int,
        default=None,
        help="hard step budget for smoke runs: --serve skips warmup and "
        "caps decode steps (active requests complete as 'step_cap', queued "
        "as 'cancelled'); --comms times exactly this many steps with "
        "minimal warmup — a regression can never hang CI",
    )
    parser.add_argument(
        "--quant",
        action="store_true",
        help="quantized-serving benchmark: int8 KV pages (and int8 "
        "weights) vs the f32 paged engine on identical greedy traffic — "
        "per-config HBM bytes incl. scale overhead, admitted tokens/HBM-"
        "byte vs f32, decode step time, greedy agreement rate and "
        "teacher-forced logit MAE; emits the QUANT_r{NN}.json artifact",
    )
    parser.add_argument(
        "--tp",
        type=int,
        default=None,
        metavar="N",
        help="tensor-parallel serving benchmark: TP=1 vs TP=N engines "
        "(dense f32 + paged int8) at fixed model size on a virtual pod, "
        "every placement resolved through the partition-rule table in "
        "parallel/sharding.py; emits the TP_r{NN}.json artifact gated "
        "on bit-identical greedy tokens, per-chip param HBM <= 0.55x "
        "and a strictly-lower decode roofline",
    )
    parser.add_argument(
        "--spec",
        action="store_true",
        help="speculative-decoding benchmark (spec/): truncated-layer "
        "and int8-weight drafters + batched verification vs plain f32 "
        "decode on identical greedy traffic; emits the SPEC_r{NN}.json "
        "artifact gated on bit-identical tokens and a decode-phase "
        "tok/s win for the truncated drafter",
    )
    parser.add_argument(
        "--draft-tokens",
        type=int,
        default=4,
        help="draft tokens K per speculative step for --spec",
    )
    parser.add_argument(
        "--draft-layers",
        type=int,
        default=None,
        help="truncated-drafter depth for --spec (default: num_layers/6 "
        "— shallow enough that drafting K tokens costs less than the "
        "one verify it saves)",
    )
    parser.add_argument(
        "--obs",
        action="store_true",
        help="observability benchmark: run the f32 and int8-KV paged "
        "serving engines under the obs tracer + jax.profiler, emit the "
        "OBS_r{NN}.json artifact (merged host+device timeline digest, "
        "per-phase decode breakdown, int8-regression attribution); the "
        "full merged Chrome trace lands in --trace-dir",
    )
    parser.add_argument(
        "--obs-fleet",
        action="store_true",
        help="fleet-observability benchmark: a multi-replica chaos fleet "
        "(replica_death + decode_stall) with distributed request tracing "
        "— per-worker Chrome-trace shards merged onto the router clock, "
        "bucket-merged fleet TTFT/TPOT percentiles, flight-recorder "
        "dumps, SLO evaluation; emits OBS_FLEET_r{NN}.json and gates on "
        "the failover being traceable under one trace id, exact "
        "percentile merging, zero lost requests and the SLO verdict",
    )
    parser.add_argument(
        "--obs-fleet-spec",
        default="replica_death@3,decode_stall@6:secs=0.2",
        help="DDLT_FAULTS schedule for --obs-fleet (must contain a "
        "replica_death: the artifact's whole point is a traceable "
        "failover)",
    )
    parser.add_argument(
        "--obs-fleet-requests",
        type=int,
        default=24,
        help="request count for --obs-fleet (enough that the death "
        "orphans in-flight work and the restarted replica rejoins "
        "mid-run)",
    )
    parser.add_argument(
        "--obs-fleet-new-tokens",
        type=int,
        default=12,
        help="per-request generation budget for --obs-fleet",
    )
    parser.add_argument(
        "--slo",
        default=(
            "ttft_p99_s=60,tpot_p99_s=10,"
            "max_error_rate=0,max_lost_requests=0"
        ),
        help="SLO spec for --obs-fleet, evaluated over the bucket-merged "
        "fleet metrics (latency limits sized for CPU chaos runs; tighten "
        "on hardware)",
    )
    parser.add_argument(
        "--comms",
        action="store_true",
        help="benchmark the explicit gradient-comms schedule "
        "(parallel/comms.py: bucketed reduce-scatter overlap, weight-"
        "update sharding, bf16 compressed wire) against the implicit "
        "GSPMD allreduce on the same model; emits the COMMS_r{NN}.json "
        "artifact (NN = the current BENCH_REVISION)",
    )
    parser.add_argument(
        "--bucket-mb",
        type=float,
        default=4.0,
        help="gradient bucket size in MB for --comms overlap modes",
    )
    parser.add_argument(
        "--accum-steps",
        type=int,
        default=2,
        help="microbatch accumulation for --comms (the overlap schedule "
        "reduce-scatters per microbatch inside the scan; >1 exercises it)",
    )
    parser.add_argument(
        "--comms-modes",
        default="implicit,overlap,overlap_wus,overlap_bf16",
        help="comma subset of comms modes to run (must include "
        "implicit,overlap); CI smokes trim compile time with "
        "implicit,overlap",
    )
    parser.add_argument(
        "--faults",
        action="store_true",
        help="chaos benchmark: run a small synthetic training job with an "
        "injected fault schedule (--faults-spec) under the in-process "
        "restart supervisor and emit the RESILIENCE_*.json artifact "
        "(faults injected, recoveries, re-done steps, recovery-overhead %%)",
    )
    parser.add_argument(
        "--faults-spec",
        default="nan_loss@4,data_stall@6:secs=0.3,preempt@9,data_death@14",
        help="DDLT_FAULTS schedule for --faults (README 'Fault tolerance' "
        "has the grammar)",
    )
    parser.add_argument(
        "--faults-max-restarts",
        type=int,
        default=2,
        help="supervisor restart budget for --faults",
    )
    parser.add_argument(
        "--goodput",
        action="store_true",
        help="goodput-ledger chaos benchmark: a short training run under "
        "the real ddlt train --max-restarts supervisor with an injected "
        "preemption + anomaly abort, 100%% of its wall classified by the "
        "goodput ledger (obs/goodput.py) and stitched across restarts; "
        "emits GOODPUT_r{NN}.json with the ledger, the supervisor-matched "
        "redone/recovery accounting and the perf-trajectory digest "
        "(obs/history.py), gated on the <=2%% unaccounted-time residual",
    )
    parser.add_argument(
        "--goodput-spec",
        default="preempt@6,nan_loss@13,nan_loss@14,nan_loss@15",
        help="DDLT_FAULTS schedule for --goodput (the default lands one "
        "exact-resume preemption AND one anomaly abort that re-does "
        "exactly 2 steps, so both restart flavors show in one ledger)",
    )
    parser.add_argument(
        "--goodput-max-restarts",
        type=int,
        default=2,
        help="supervisor restart budget for --goodput",
    )
    parser.add_argument(
        "--attrib",
        action="store_true",
        help="attribution benchmark (obs/attrib.py + obs/ledger.py): "
        "per-program cost_analysis flops/bytes + memory_analysis "
        "residency over the serve engines / spec decoder / train step, "
        "HBM-ledger owner totals reconciled against live device bytes, "
        "straggler phase timing, the analytic compute-vs-collective "
        "split and a ledger-forecast admission demo; emits "
        "ATTRIB_r{NN}.json gated on program coverage, the 1%% "
        "owner-vs-live match, the <=5%% unaccounted-HBM residual and "
        "forecast backpressure",
    )
    parser.add_argument(
        "--serve-faults",
        action="store_true",
        help="serving chaos benchmark: the supervised replica fleet "
        "(serve/fleet.py) under an injected serve-side fault schedule vs "
        "the identical fault-free fleet; emits SERVE_RESILIENCE_r{NN}."
        "json and gates on zero lost requests, bit-identical greedy "
        "failover, quarantine precision and recovery overhead",
    )
    parser.add_argument(
        "--serve-faults-spec",
        default="replica_death@3,decode_nan@5,decode_stall@8:secs=0.2",
        help="DDLT_FAULTS schedule for --serve-faults (serve-side kinds "
        "are dealt one-per-replica; README 'Serving fault tolerance' has "
        "the grammar)",
    )
    parser.add_argument(
        "--serve-replicas",
        type=int,
        default=2,
        help="fleet width for --serve-faults (>= 2 so replica_death "
        "leaves a survivor to fail over to)",
    )
    parser.add_argument(
        "--serve-max-restarts",
        type=int,
        default=1,
        help="per-replica restart budget for --serve-faults",
    )
    parser.add_argument(
        "--serve-faults-requests",
        type=int,
        default=192,
        help="request count for --serve-faults (independent of --serve-"
        "requests: the chaos run needs enough work that the fixed "
        "restart cost amortizes — the recovery-overhead gate measures "
        "steady-state resilience, not cold-start arithmetic; at the "
        "default the restarted replica rejoins MID-RUN and shares the "
        "remaining load, which is the recovery story worth measuring)",
    )
    parser.add_argument(
        "--serve-faults-trials",
        type=int,
        default=2,
        help="wall-time trials per side for --serve-faults; the overhead "
        "gate compares per-side MIN walls (host contention only adds "
        "time, so the min is the noise-robust estimate; correctness "
        "gates always use the first pair)",
    )
    parser.add_argument(
        "--serve-faults-new-tokens",
        type=int,
        default=48,
        help="per-request generation budget for --serve-faults (its own "
        "knob, not --max-new-tokens: the run must outlast the restarted "
        "replica's respawn or the overhead gate measures a fleet that "
        "never got its capacity back)",
    )
    parser.add_argument(
        "--overload",
        action="store_true",
        help="overload-survival chaos benchmark: a tenant-classed fleet "
        "(premium/standard/best_effort) under a best-effort arrival "
        "burst with scarce KV pages, vs an ample-capacity fault-free "
        "twin of the same deterministic schedule; emits "
        "OVERLOAD_r{NN}.json and gates on premium tail isolation, "
        "bit-identical preempted-then-resumed streams, zero lost "
        "requests and best-effort-only shedding",
    )
    parser.add_argument(
        "--overload-burst",
        default="burst@1:tenant=best_effort:rps=40:secs=4:at=0.5",
        help="DDLT_FAULTS burst spec consumed at traffic-schedule build "
        "(utils/faults.py 'burst' kind) — the injected overload",
    )
    parser.add_argument(
        "--overload-duration-s",
        type=float,
        default=8.0,
        help="traffic schedule length in seconds for --overload",
    )
    parser.add_argument(
        "--overload-speedup",
        type=float,
        default=1.0,
        help="replay the --overload schedule compressed by this factor "
        "(arrival order is preserved)",
    )
    parser.add_argument(
        "--overload-new-tokens",
        type=int,
        default=16,
        help="per-request generation budget for --overload (long enough "
        "that a preempted stream has tokens worth preserving)",
    )
    parser.add_argument(
        "--overload-kv-pages",
        type=int,
        default=11,
        help="KV pages per replica for --overload (page_size 8, 4 pages "
        "per sequence: 11 pages under 3 slots means admission hits PAGE "
        "pressure with a slot free — the preempt-then-shed ladder, not "
        "just slot queueing)",
    )
    parser.add_argument(
        "--overload-preempt-budget",
        type=int,
        default=2,
        help="per-request preemption budget for --overload (past it a "
        "request finishes terminal 'preempted' instead of starving)",
    )
    parser.add_argument(
        "--overload-max-redeliveries",
        type=int,
        default=1,
        help="router redelivery budget for --overload (a shed result is "
        "retried on another replica this many times before it finishes "
        "terminal 'shed' with its retry_after_s hint)",
    )
    parser.add_argument(
        "--overload-premium-ttft-limit",
        type=float,
        default=2.5,
        help="premium-isolation gate for --overload: premium TTFT p99 "
        "bound in seconds (doubled in --steps-cap smoke runs)",
    )
    parser.add_argument(
        "--overload-premium-tpot-limit",
        type=float,
        default=0.5,
        help="premium-isolation gate for --overload: premium TPOT p99 "
        "bound in seconds (doubled in --steps-cap smoke runs)",
    )
    parser.add_argument(
        "--tier",
        action="store_true",
        help="host-memory KV tier benchmark (serve/kv_tier.py): "
        "spilled-then-restored greedy streams pinned bit-identical to "
        "never-spilled (paged f32 + int8, and vs the dense layout), "
        "then a session-oversubscription phase (working set 4-10x the "
        "page pool) measuring prefix-hit rate and admitted-tokens-per-"
        "computed-HBM-byte with and without the tier, plus a fits-in-"
        "HBM decode-throughput parity check; emits TIER_r{NN}.json",
    )
    parser.add_argument(
        "--host-pages",
        type=int,
        default=None,
        help="host-pool size in pages for --tier (default: sized to "
        "hold every session's prefix working set, the ample-host case "
        "the hit-rate gate measures)",
    )
    parser.add_argument(
        "--tier-policy",
        default="lru",
        choices=("lru", "fifo"),
        help="host-pool replacement policy for --tier",
    )
    parser.add_argument(
        "--tier-sessions",
        type=int,
        default=24,
        help="distinct sessions (each with its own re-queried prefix) "
        "for the --tier oversubscription phase; together with the page "
        "pool this sets the oversubscription factor",
    )
    parser.add_argument(
        "--tier-rounds",
        type=int,
        default=3,
        help="measured re-query rounds over the session set for --tier "
        "(after an unmeasured seeding round)",
    )
    parser.add_argument(
        "--ckpt-faults",
        action="store_true",
        help="durable-state chaos benchmark: verified checkpoint "
        "generations under injected corruption (ckpt_corrupt / "
        "ckpt_torn), corrupt-latest training resume landing on the exact "
        "newest VERIFIED step, live weight reload across a serving fleet "
        "pinned bit-identical to a fresh engine, and the manifest verify-"
        "overhead budget; emits CKPT_DURABLE_r{NN}.json",
    )
    parser.add_argument(
        "--ckpt-faults-spec",
        default="ckpt_corrupt@4:mode=flip",
        help="DDLT_FAULTS schedule for the --ckpt-faults training phase "
        "(generation-opportunity keyed: @4 corrupts the 4th — latest — "
        "finalized generation of the run)",
    )
    parser.add_argument(
        "--ckpt-verify-overhead-limit",
        type=float,
        default=10.0,
        help="verify-overhead gate for --ckpt-faults (manifest build + "
        "verification wall as a percent of the save wall)",
    )
    parser.add_argument(
        "--serve-overhead-limit",
        type=float,
        default=30.0,
        help="recovery-overhead gate for --serve-faults (percent of the "
        "fault-free wall; CI smokes with tiny workloads raise it — a "
        "fixed restart cost dominates a short run)",
    )
    parser.add_argument(
        "--report",
        default=None,
        help="artifact output path for --faults/--quant/--comms/--obs "
        "(default: <KIND>_r{NN}.json at the current BENCH_REVISION)",
    )
    parser.add_argument(
        "--data",
        default=None,
        choices=("tfrecords", "native", "raw"),
        help="feed the step from a real input pipeline instead of a "
        "device-resident synthetic batch; reports fed_vs_synthetic",
    )
    parser.add_argument(
        "--data-dir",
        default=None,
        help="TFRecord shard directory for --data (default: a generated "
        "synthetic-JPEG set under ~/.cache/ddlt/bench-shards)",
    )
    parser.add_argument(
        "--data-images",
        type=int,
        default=4096,
        help="images in the generated bench shard set",
    )
    parser.add_argument(
        "--prefetch",
        type=int,
        default=4,
        help="host->device prefetch depth for --data",
    )
    args = parser.parse_args()
    if args.fit and args.model == "lm":
        parser.error("--fit is not supported for --model lm")
    if args.quant and (args.serve or args.devices or args.data
                       or args.faults or args.comms or args.obs):
        parser.error(
            "--quant is exclusive with --serve/--devices/--data/"
            "--faults/--comms/--obs"
        )
    if args.obs and (args.serve or args.devices or args.data
                     or args.faults or args.comms):
        parser.error(
            "--obs is exclusive with --serve/--devices/--data/"
            "--faults/--comms"
        )
    if args.obs_fleet and (args.serve or args.devices or args.data
                           or args.faults or args.comms or args.quant
                           or args.obs or args.spec or args.serve_faults):
        parser.error(
            "--obs-fleet is exclusive with the other benchmark modes"
        )
    if args.obs_fleet and args.serve_replicas < 2:
        parser.error(
            "--obs-fleet needs --serve-replicas >= 2 (replica_death "
            "must leave a survivor for the failover chain to land on)"
        )
    if args.tp is not None and args.tp < 2:
        parser.error("--tp must be >= 2 (TP=1 is the built-in baseline)")
    if args.tp and (args.serve or args.devices or args.data
                    or args.faults or args.comms or args.quant
                    or args.obs or args.obs_fleet or args.spec
                    or args.serve_faults or args.ckpt_faults
                    or args.goodput or args.attrib or args.overload):
        parser.error("--tp is exclusive with the other benchmark modes")
    if args.spec and (args.serve or args.devices or args.data
                      or args.faults or args.comms or args.quant
                      or args.obs or args.serve_faults):
        parser.error(
            "--spec is exclusive with --serve/--devices/--data/"
            "--faults/--comms/--quant/--obs/--serve-faults"
        )
    if args.spec and args.draft_tokens < 1:
        parser.error("--draft-tokens must be >= 1")
    if args.spec and args.draft_layers is not None and args.draft_layers < 1:
        parser.error("--draft-layers must be >= 1")
    if args.serve and args.devices:
        # the scaling dispatch would otherwise win silently and emit a
        # wrong-schema artifact where the caller scripted a SERVE one
        parser.error("--serve and --devices are mutually exclusive")
    if args.faults and (args.serve or args.devices or args.data):
        parser.error("--faults is exclusive with --serve/--devices/--data")
    if args.goodput and (args.serve or args.devices or args.data
                         or args.faults or args.comms or args.quant
                         or args.obs or args.obs_fleet or args.spec
                         or args.serve_faults or args.ckpt_faults):
        parser.error(
            "--goodput is exclusive with the other benchmark modes"
        )
    if args.attrib and (args.serve or args.devices or args.data
                        or args.faults or args.comms or args.quant
                        or args.obs or args.obs_fleet or args.spec
                        or args.serve_faults or args.ckpt_faults
                        or args.goodput):
        parser.error(
            "--attrib is exclusive with the other benchmark modes"
        )
    if args.serve_faults and (args.serve or args.devices or args.data
                              or args.faults or args.comms or args.quant
                              or args.obs):
        parser.error(
            "--serve-faults is exclusive with --serve/--devices/--data/"
            "--faults/--comms/--quant/--obs"
        )
    if args.serve_faults and args.serve_replicas < 2:
        parser.error(
            "--serve-faults needs --serve-replicas >= 2 (replica_death "
            "must leave a survivor to fail over to)"
        )
    if args.ckpt_faults and (args.serve or args.devices or args.data
                             or args.faults or args.comms or args.quant
                             or args.obs or args.obs_fleet or args.spec
                             or args.serve_faults):
        parser.error(
            "--ckpt-faults is exclusive with the other benchmark modes"
        )
    if args.overload and (args.serve or args.devices or args.data
                          or args.faults or args.comms or args.quant
                          or args.obs or args.obs_fleet or args.spec
                          or args.serve_faults or args.ckpt_faults
                          or args.goodput or args.attrib):
        parser.error(
            "--overload is exclusive with the other benchmark modes"
        )
    if args.overload and args.serve_replicas < 2:
        parser.error(
            "--overload needs --serve-replicas >= 2 (premium isolation "
            "across a fleet is the claim; one replica proves only local "
            "queueing)"
        )
    if args.overload and args.overload_preempt_budget < 0:
        parser.error("--overload-preempt-budget must be >= 0")
    if args.tier and (args.serve or args.devices or args.data
                      or args.faults or args.comms or args.quant
                      or args.obs or args.obs_fleet or args.spec
                      or args.serve_faults or args.ckpt_faults
                      or args.goodput or args.attrib or args.overload
                      or args.tp):
        parser.error("--tier is exclusive with the other benchmark modes")
    if args.tier and args.host_pages is not None and args.host_pages < 1:
        parser.error("--host-pages must be >= 1")
    if args.tier and (args.tier_sessions < 2 or args.tier_rounds < 1):
        parser.error("--tier needs >= 2 sessions and >= 1 round")
    if args.comms:
        if args.serve or args.devices or args.data or args.faults:
            parser.error(
                "--comms is exclusive with --serve/--devices/--data/--faults"
            )
        if args.model.startswith("bert") or args.model == "lm":
            # bert's adamw chains clip_by_global_norm (invalid under
            # weight-update sharding — shard-norm clipping) and the lm
            # builder hand-rolls its TrainState; the image models are the
            # comparison the artifact documents
            parser.error("--comms supports the image models (e.g. resnet50)")
        if args.steps_cap is not None and args.steps_cap < 1:
            parser.error("--steps-cap must be >= 1 with --comms")

    if args.small:
        args.batch_size, args.image_size = 16, 64
        args.num_iters, args.num_batches_per_iter, args.num_warmup = 2, 2, 1
        args.data_images = min(args.data_images, 128)
        if args.model.startswith("bert"):
            args.batch_size, args.seq_len = 4, 32

    from distributeddeeplearning_tpu.utils.hardware import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    if args.lint:
        # preflight: a committed artifact must never be produced from a
        # tree with open findings — run both analyzer layers and abort
        # BEFORE any benchmark phase when anything is open.  ``ddlt lint``
        # runs as a child on its own virtual CPU pod, so this parent stays
        # off the backend for the modes whose workers need the chip; the
        # child reports the audits it had to skip on its stderr.
        import subprocess

        lint = subprocess.run(
            [sys.executable, "-m", "distributeddeeplearning_tpu.cli.main",
             "lint"],
            stdout=sys.stderr,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if lint.returncode != 0:
            print(
                "[bench] --lint preflight FAILED: refusing to benchmark a "
                "tree with open findings",
                file=sys.stderr,
            )
            return 1
        print("[bench] --lint preflight: 0 findings", file=sys.stderr)
    from distributeddeeplearning_tpu.serve.fleet import ReplicaPlacementError

    try:
        return _dispatch(args)
    except ReplicaPlacementError as exc:
        # a fleet mode asked for more replica workers than the host has
        # chips to give one each
        print(f"[bench] {exc}", file=sys.stderr)
        return 2


def _dispatch(args) -> int:
    """Run the one mode the flags select.  The modes that start children
    which need the chip (``ddlt train`` subprocesses, fleet workers) keep
    this parent off the backend until those children have exited — a chip
    belongs to one process at a time."""
    if args.faults:
        return _run_faults(args)
    if args.goodput:
        return _run_goodput(args)
    if args.attrib:
        return _run_attrib(args)
    if args.serve_faults:
        return _run_serve_faults(args)
    if args.overload:
        return _run_overload(args)
    if args.tier:
        return _run_tier(args)
    if args.ckpt_faults:
        return _run_ckpt_faults(args)
    if args.quant:
        return _run_quant(args)
    if args.tp:
        return _run_tp(args)
    if args.spec:
        return _run_spec(args)
    if args.obs:
        return _run_obs(args)
    if args.obs_fleet:
        return _run_obs_fleet(args)
    if args.comms:
        return _run_comms(args)
    if args.devices:
        return _run_scaling(args)
    if args.serve:
        return _run_serve(args)
    if args.roofline:
        return _run_roofline(args)
    if args.data:
        return _run_data(args)
    return _run_single(args)


if __name__ == "__main__":
    sys.exit(main())
