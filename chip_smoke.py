#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once through the entry points a user calls, at the
full width of the one LM the repo both trains and serves
(``models/pipelined_transformer``: 12 layers, d_model 768, 12 heads of 64,
d_ff 3072, vocab 32768), with seeded random weights:

- **kernels**: every Pallas kernel compiled on the device (not interpreted)
  at the served and trained geometry and compared with its XLA reference —
  ``flash_decode`` decode / chunk-prefill / K+1-verify over f32 and int8
  pools, ``flash_attention`` forward, dq and dk/dv, causal, bf16;
- **serve**: ``ddlt serve --synthetic`` over the paged cache (f32, then
  int8 KV) and the dense cache, more requests than slots and a shared
  prefix so slots and prefix pages are reused — run twice, the second a
  fresh process on a warm compile cache, and the greedy tokens compared;
- **train**: ``ddlt train transformer --seq_len 2048 --batch_size 8
  --attention flash --compute_dtype bfloat16``, preempted once so that a
  checkpoint is saved, then resumed by a second invocation that restores it.

On a host with four chips or more the same script also trains on a
data×fsdp 2×2 mesh and with ``--tensor 2`` (depth cut to stay quick); the
dense serve run shards its slots over every chip by itself.

A chip belongs to one process at a time, so this parent never imports JAX:
it runs one child per phase, in turn, all sharing the persistent compile
cache (``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the
checkout).  Any phase that raises, any request that finishes ``error``, any
program that asked for a Pallas kernel and does not contain one, fails the
run.  Without an accelerator, or without the rest of the repo beside it, the
script exits non-zero and prints no result.  The times it prints are set-up
facts (cold wall, compile wall, warm wall on the second invocation), not
metrics.  The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "distributeddeeplearning_tpu"

#: exit codes: 0 pass, 1 a phase failed, 2 the repo is not beside this
#: file, 3 JAX found no accelerator
EXIT_FAILED, EXIT_NO_REPO, EXIT_NO_ACCELERATOR = 1, 2, 3

#: Kernel-vs-reference tolerances, as max |kernel - reference| over
#: max(1, max |reference|), set by the dtype of the kernel's operands.
#: The references run at full f32 matmul precision.  On the TPU an f32
#: matmul at default precision is ONE bf16 pass through the MXU — in the
#: flash_decode kernels exactly as in every XLA matmul of the model — so
#: f32 and int8 pools (dequantized to f32 in the tile) are held to a few
#: bf16 ulps of the scores, which the softmax carries into the context;
#: flash_attention takes bf16 operands AND rounds its output to bf16.
TOLERANCE = {"float32": 1e-2, "int8": 1e-2, "bfloat16": 3e-2}


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything a phase is sized by — ``FULL`` on the chip, a tiny
    instance in the tier-1 test that runs the same phase functions on the
    CPU pod."""

    # the LM (widths are never cut; depth may be)
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    d_ff: int = 3072
    vocab_size: int = 32768
    # serve traffic
    requests: int = 10
    batch_slots: int = 4
    prompt_len: int = 200       # random tail, on top of the shared prefix
    shared_prefix_len: int = 128
    max_new_tokens: int = 16
    max_seq: int = 512
    page_size: int = 64         # the serve default
    prefill_chunk: int = 64     # the serve default
    # train job
    seq_len: int = 2048
    batch_size: int = 8
    steps_per_epoch: int = 3
    epochs: int = 2
    preempt_at: int = 4         # one step into the second epoch
    # the cut used for the extra multi-chip train runs
    multichip_layers: int = 4
    multichip_batch_size: int = 4
    # kernel geometry (slots, history blocks, verify width)
    kernel_slots: int = 8
    kernel_blocks: int = 8
    verify_tokens: int = 5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


FULL = Size()


class SmokeFailure(AssertionError):
    """A phase's result is not what the contract asks for."""


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# --------------------------------------------------------------------------
# child side: the phases (these import JAX; the parent below never does)
# --------------------------------------------------------------------------


class _CompileLog:
    """Counts this process's compile requests (each one either compiles
    or loads from the persistent cache; the wall covers both) and its
    persistent-cache hits and misses, from JAX's own monitoring events —
    reported, never assumed (a donated-state jit may compile twice: output
    layouts feed back as input layouts)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compile_requests = 0
        self.compile_wall_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_requests += 1
            self.compile_wall_s += duration

    def snapshot(self) -> Dict[str, Any]:
        return {
            "compile_requests": self.compile_requests,
            "compile_wall_s": round(self.compile_wall_s, 2),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_misses": self.cache_misses,
        }


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def describe_device() -> Dict[str, Any]:
    """The device as JAX reports it, plus the versions that ran."""
    import importlib.metadata as metadata

    import jax

    from distributeddeeplearning_tpu.utils.hardware import device_summary

    out = device_summary()
    # the capacity the serve scheduler's HBM-forecast admission runs
    # against (obs/ledger.py reads the same field); None off the chip
    stats = jax.local_devices()[0].memory_stats() or {}
    out["hbm_bytes_limit"] = stats.get("bytes_limit")
    out["jax"] = jax.__version__
    try:
        out["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        out["libtpu"] = None
    return out


def _relative_error(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / max(1.0, np.abs(ref).max()))


def phase_kernels(size: Size, *, on_chip: bool) -> Dict[str, Any]:
    """Compile every Pallas kernel at the phase's geometry and compare it
    with its XLA reference on seeded inputs.  Each kernel is called twice
    before anything is read.  On the chip the kernel must be a Mosaic call
    in its lowered program (off the chip it interprets, and the comparison
    alone is checked)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.obs.attrib import MOSAIC_CALL_TARGET
    from distributeddeeplearning_tpu.ops import flash_decode as fd
    from distributeddeeplearning_tpu.ops.flash_attention import (
        _dense_attention,
        flash_attention,
    )

    rng = np.random.default_rng(0)
    b, h, hd = size.kernel_slots, size.num_heads, size.head_dim
    ps, nb = size.page_size, size.kernel_blocks
    pages = b * nb + 1  # page 0 is the scratch page, as in the engines
    history = nb * ps
    rows: List[Dict[str, Any]] = []
    failures: List[str] = []

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))

    def compare(name: str, dtype: str, kernel_fn, reference_fn, args):
        kernel = jax.jit(kernel_fn)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(reference_fn)(*args)
        kernel(*args)
        got = jax.block_until_ready(kernel(*args))  # second call
        flat_got = jax.tree_util.tree_leaves(got)
        flat_ref = jax.tree_util.tree_leaves(ref)
        err = max(_relative_error(g, r) for g, r in zip(flat_got, flat_ref))
        mosaic = kernel.lower(*args).as_text().count(MOSAIC_CALL_TARGET)
        row = {
            "kernel": name, "dtype": dtype, "max_rel_err": err,
            "tolerance": TOLERANCE[dtype], "mosaic_calls": mosaic,
        }
        rows.append(row)
        print(f"[kernels] {json.dumps(row)}", flush=True)
        # every kernel is tried before the phase fails, so one run shows
        # all of them
        if not err <= TOLERANCE[dtype]:
            failures.append(
                f"{name}: error {err:.3e} vs its reference exceeds the "
                f"{dtype} tolerance {TOLERANCE[dtype]:.0e}"
            )
        if on_chip and mosaic < 1:
            failures.append(
                f"{name}: no Mosaic custom call in the lowered program"
            )

    tables = jnp.asarray(
        1 + np.arange(b)[:, None] * nb + np.arange(nb)[None], jnp.int32
    )
    pos = jnp.asarray(rng.integers(ps, history, size=b), jnp.int32)
    # pool pages as the engines hold them: heads folded into the minor axis
    for pool in ("float32", "int8"):
        if pool == "int8":
            k_l, v_l = (
                jnp.asarray(rng.integers(
                    -127, 128, size=(pages, ps, h * hd), dtype=np.int8
                ))
                for _ in range(2)
            )
            k_s, v_s = (
                jnp.asarray(rng.uniform(
                    0.005, 0.02, size=(pages, ps, h)
                ).astype(np.float32))
                for _ in range(2)
            )
        else:
            k_l, v_l = normal(pages, ps, h * hd), normal(pages, ps, h * hd)
            k_s = v_s = None
        q3, k_t, v_t = normal(b, h, hd), normal(b, h, hd), normal(b, h, hd)
        compare(
            f"flash_decode.decode[{pool}]", pool,
            functools.partial(
                fd.decode_attention_paged, page_size=ps, kernel="pallas"
            ),
            functools.partial(
                fd.decode_attention_paged, page_size=ps, kernel="gather"
            ),
            (q3, k_l, v_l, k_s, v_s, k_t, v_t, pos, tables),
        )
        chunk = size.prefill_chunk
        offset = history - chunk - 3  # mid-page, the prefix-hit shape
        compare(
            f"flash_decode.chunk_prefill[{pool}]", pool,
            functools.partial(
                fd.chunk_attention, page_size=ps, kernel="pallas"
            ),
            functools.partial(
                fd.chunk_attention, page_size=ps, kernel="gather"
            ),
            (normal(chunk, h, hd), k_l, v_l, k_s, v_s, tables[0],
             offset + jnp.arange(chunk, dtype=jnp.int32)),
        )
        if pool == "float32":  # speculative verify is f32-only upstream
            k1 = size.verify_tokens
            posmat = (pos - k1)[:, None] + jnp.arange(k1, dtype=jnp.int32)
            compare(
                "flash_decode.verify[float32]", pool,
                functools.partial(
                    fd.verify_attention_paged, page_size=ps,
                    kernel="pallas",
                ),
                functools.partial(
                    fd.verify_attention_paged, page_size=ps,
                    kernel="gather",
                ),
                (normal(b, k1, h, hd), k_l, v_l, tables, posmat),
            )

    # flash_attention at the trained geometry: forward, then dq and dk/dv
    # through the custom VJP, against plain autodiff of the dense reference
    shape = (size.batch_size, size.seq_len, h, hd)
    q, k, v, cot = (normal(*shape).astype(jnp.bfloat16) for _ in range(4))

    def flash(q, k, v):
        return flash_attention(q, k, v, None, dtype=jnp.bfloat16, causal=True)

    def dense(q, k, v):
        return _dense_attention(
            q, k, v, None, dtype=jnp.bfloat16, causal=True
        )

    def grads(attention):
        def loss(q, k, v):
            return (attention(q, k, v).astype(jnp.float32)
                    * cot.astype(jnp.float32)).sum()

        return jax.grad(loss, argnums=(0, 1, 2))

    compare("flash_attention.forward", "bfloat16", flash, dense, (q, k, v))
    compare(
        "flash_attention.backward[dq,dk,dv]", "bfloat16",
        grads(flash), grads(dense), (q, k, v),
    )
    if on_chip and rows[-1]["mosaic_calls"] < 3:
        failures.append(
            "flash_attention backward: expected the forward, dq and dk/dv "
            f"kernels, found {rows[-1]['mosaic_calls']} Mosaic call(s)"
        )
    _require(not failures, "; ".join(failures))
    return {"kernels": rows}


def _ddlt(argv: List[str], *, quiet: bool = False) -> int:
    """The ``ddlt`` entry point, in this process.  ``quiet`` drops what it
    prints to stdout (the serve stats line, read from ``--report``)."""
    import contextlib
    import io

    from distributeddeeplearning_tpu.cli.main import main as cli_main

    print(f"[ddlt] {' '.join(argv)}", flush=True)
    with contextlib.redirect_stdout(io.StringIO()) if quiet else (
        contextlib.nullcontext()
    ):
        return cli_main(argv)


def _model_flags(size: Size, *, dashes: str) -> List[str]:
    sep = dashes  # `ddlt serve` spells flags with '-', the workloads with '_'
    return [
        f"--num{sep}layers", str(size.num_layers),
        f"--d{sep}model", str(size.d_model),
        f"--num{sep}heads", str(size.num_heads),
        f"--d{sep}ff", str(size.d_ff),
        f"--vocab{sep}size", str(size.vocab_size),
    ]


SERVE_CONFIGS = (
    ("paged_f32", ["--kv-layout", "paged"]),
    ("paged_int8", ["--kv-layout", "paged", "--quantize-kv", "int8"]),
    ("dense_f32", ["--kv-layout", "dense"]),
)


def check_serve(name: str, rc: int, stats: Dict[str, Any], size: Size, *,
                on_chip: bool) -> None:
    """One ``ddlt serve --synthetic`` run is a pass."""
    _require(rc == 0, f"serve[{name}]: ddlt serve exited {rc}")
    reasons = stats["finish_reasons"]
    _require(
        set(reasons) <= {"length", "eos"}
        and sum(reasons.values()) == size.requests,
        f"serve[{name}]: finish reasons {reasons} — every one of the "
        f"{size.requests} requests must finish length or eos",
    )
    _require(stats["errors"] == 0, f"serve[{name}]: errors {stats['errors']}")
    _require(
        stats["generated_tokens"] > 0, f"serve[{name}]: no tokens generated"
    )
    if name.startswith("paged"):
        _require(
            stats["prefix_hit_rate"] > 0,
            f"serve[{name}]: no prompt token was served from a shared "
            "prefix page",
        )
    if on_chip:
        _require(
            stats["platform"] == "tpu",
            f"serve[{name}]: ran on {stats['platform']!r}",
        )
        _require(
            stats["decode_impl"] == "pallas",
            f"serve[{name}]: asked for the flash kernel, decode ran "
            f"{stats['decode_impl']!r}",
        )
        calls = stats.get("mosaic_calls") or {}
        _require(
            len(calls) == 2 and all(n >= 1 for n in calls.values()),
            f"serve[{name}]: a program that asked for a Pallas kernel "
            f"holds no Mosaic call: {calls}",
        )


def phase_serve(size: Size, workdir: str, *, on_chip: bool) -> Dict[str, Any]:
    """``ddlt serve --synthetic`` once per cache configuration."""
    runs = {}
    for name, flags in SERVE_CONFIGS:
        report = os.path.join(workdir, f"serve_{name}.json")
        t0 = time.time()
        rc = _ddlt(quiet=True, argv=[
            "serve", "--synthetic", *_model_flags(size, dashes="-"),
            "--requests", str(size.requests),
            "--batch-slots", str(size.batch_slots),
            "--prompt-len", str(size.prompt_len),
            "--shared-prefix-len", str(size.shared_prefix_len),
            "--max-new-tokens", str(size.max_new_tokens),
            "--max-seq", str(size.max_seq),
            "--page-size", str(size.page_size),
            "--prefill-chunk", str(size.prefill_chunk),
            "--seed", "0", "--report", report, *flags,
        ])
        wall = time.time() - t0
        with open(report) as f:
            stats = json.load(f)
        check_serve(name, rc, stats, size, on_chip=on_chip)
        keep = (
            "platform", "device_kind", "device_count", "mesh_devices",
            "kv_layout", "kv_dtype", "decode_kernel", "decode_impl",
            "mosaic_calls", "requests", "finish_reasons", "errors",
            "generated_tokens", "prompt_tokens", "decode_steps",
            "prefix_hit_rate", "prefill_compiles", "kv_bytes",
            "token_digest",
        )
        runs[name] = {k: stats.get(k) for k in keep}
        runs[name]["wall_s"] = round(wall, 1)
        print(f"[serve] {name}: {json.dumps(runs[name])}", flush=True)
    return {"runs": runs}


def _train_argv(size: Size, ckpt: str, metrics: str, *, layers: int,
                batch_size: int, extra: List[str]) -> List[str]:
    return [
        "train", "transformer", "--max-restarts", "0", *extra,
        *_model_flags(dataclasses.replace(size, num_layers=layers),
                      dashes="_"),
        "--seq_len", str(size.seq_len),
        "--batch_size", str(batch_size),
        "--attention", "flash", "--compute_dtype", "bfloat16",
        "--epochs", str(size.epochs),
        "--steps_per_epoch", str(size.steps_per_epoch),
        # the seeded synthetic stream holds ONE global batch, so the loss
        # of a handful of steps falls visibly (a fresh random batch per
        # step would sit at ln(vocab) whatever the optimizer did)
        "--train_examples", "1", "--seed", "0",
        "--save_filepath", ckpt, "--metrics_path", metrics,
    ]


def _loss_rows(metrics: str) -> List[Dict[str, Any]]:
    with open(metrics) as f:
        return [json.loads(line) for line in f if line.strip()]


def phase_train_first(size: Size, workdir: str, *, layers: int,
                      batch_size: int, mesh_flags: List[str]) -> Dict[str, Any]:
    """First invocation: train through the first epoch, then take the
    injected preemption — the emergency checkpoint is the save."""
    from distributeddeeplearning_tpu.train.checkpoint import (
        latest_verified_step_in_dir,
    )
    from distributeddeeplearning_tpu.train.resilience import (
        RESUMABLE_EXIT_CODE,
    )

    ckpt = os.path.join(workdir, "ckpt")
    metrics = os.path.join(workdir, "train_metrics.jsonl")
    rc = _ddlt(_train_argv(
        size, ckpt, metrics, layers=layers, batch_size=batch_size,
        extra=["--faults", f"preempt@{size.preempt_at}", *mesh_flags],
    ))
    _require(
        rc == RESUMABLE_EXIT_CODE,
        f"train: the preempted run exited {rc}, not the resumable "
        f"{RESUMABLE_EXIT_CODE}",
    )
    saved = latest_verified_step_in_dir(ckpt)
    _require(
        saved == size.preempt_at,
        f"train: newest verified checkpoint is step {saved}, the "
        f"preemption was at step {size.preempt_at}",
    )
    rows = _loss_rows(metrics)
    _require(len(rows) == 1, f"train: expected one epoch row, got {rows}")
    return {"exit_code": rc, "checkpoint_step": saved,
            "first_epoch_loss": rows[0]["train_loss"]}


def phase_train_resume(size: Size, workdir: str, *, layers: int,
                       batch_size: int, mesh_flags: List[str]) -> Dict[str, Any]:
    """Second invocation, same flags: restore the checkpoint, finish."""
    import math

    from distributeddeeplearning_tpu.train.checkpoint import (
        latest_verified_step_in_dir,
    )

    ckpt = os.path.join(workdir, "ckpt")
    metrics = os.path.join(workdir, "train_metrics.jsonl")
    total = size.epochs * size.steps_per_epoch
    rc = _ddlt(_train_argv(
        size, ckpt, metrics, layers=layers, batch_size=batch_size,
        extra=mesh_flags,
    ))
    _require(rc == 0, f"train: the resumed run exited {rc}")
    final = latest_verified_step_in_dir(ckpt)
    _require(
        final == total,
        f"train: the run's last checkpoint is step {final}, it should "
        f"have finished at step {total}",
    )
    rows = _loss_rows(metrics)
    losses = [row["train_loss"] for row in rows]
    _require(
        len(losses) == size.epochs and all(math.isfinite(x) for x in losses),
        f"train: epoch losses {losses}",
    )
    _require(
        losses[-1] < losses[0],
        f"train: loss did not fall — first epoch {losses[0]:.4f}, last "
        f"epoch {losses[-1]:.4f}",
    )
    return {"steps": total, "restored_from_step": size.preempt_at,
            "epoch_losses": losses}


def run_phase(phase: str, size: Size, workdir: str, *, on_chip: bool,
              layers: Optional[int] = None, batch_size: Optional[int] = None,
              mesh_flags: Optional[List[str]] = None) -> Dict[str, Any]:
    """One phase in THIS process (a child of ``main``, or the test)."""
    from distributeddeeplearning_tpu.utils.hardware import (
        enable_compilation_cache,
    )

    t0 = time.time()
    cache_dir = enable_compilation_cache()
    entries_before = _cache_entries(cache_dir)
    log = _CompileLog()
    device = describe_device()
    if on_chip and device["platform"] == "cpu":
        print("chip_smoke: JAX found no accelerator", file=sys.stderr)
        raise SystemExit(EXIT_NO_ACCELERATOR)
    train_kw = dict(
        layers=layers or size.num_layers,
        batch_size=batch_size or size.batch_size,
        mesh_flags=mesh_flags or [],
    )
    if phase == "kernels":
        result = phase_kernels(size, on_chip=on_chip)
    elif phase == "serve":
        result = phase_serve(size, workdir, on_chip=on_chip)
    elif phase == "train_first":
        result = phase_train_first(size, workdir, **train_kw)
    elif phase == "train_resume":
        result = phase_train_resume(size, workdir, **train_kw)
    else:
        raise ValueError(f"unknown phase {phase!r}")
    result.update(
        phase=phase, device=device, wall_s=round(time.time() - t0, 1),
        cache_dir=cache_dir, cache_entries_before=entries_before,
        cache_entries_after=_cache_entries(cache_dir), **log.snapshot(),
    )
    return result


def _child_main(args) -> int:
    sys.path.insert(0, HERE)
    mesh_flags = args.mesh_flags.split() if args.mesh_flags else []
    try:
        result = run_phase(
            args.phase, FULL, args.workdir, on_chip=True,
            layers=args.layers, batch_size=args.batch_size,
            mesh_flags=mesh_flags,
        )
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED {exc}", file=sys.stderr)
        return EXIT_FAILED
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


# --------------------------------------------------------------------------
# parent side: no JAX here
# --------------------------------------------------------------------------


def _run_child(label: str, phase: str, workdir: str, **kw: Any) -> Dict[str, Any]:
    out = os.path.join(workdir, f"result_{label}.json")
    argv = [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--workdir", workdir, "--out", out]
    for key, value in kw.items():
        if value:  # '=' form: a mesh flag value itself starts with '--'
            argv.append(f"--{key.replace('_', '-')}={value}")
    print(f"\n=== chip_smoke phase {label} ===", flush=True)
    t0 = time.time()
    rc = subprocess.run(argv, cwd=HERE).returncode
    if rc == EXIT_NO_ACCELERATOR:
        raise SystemExit(EXIT_NO_ACCELERATOR)
    if rc != 0:
        print(f"chip_smoke: phase {label} exited {rc}", file=sys.stderr)
        raise SystemExit(EXIT_FAILED)
    with open(out) as f:
        result = json.load(f)
    result["process_wall_s"] = round(time.time() - t0, 1)
    print(
        f"=== {label}: ok in {result['process_wall_s']} s (compile requests "
        f"{result['compile_requests']} taking {result['compile_wall_s']} "
        f"s, cache hits {result['persistent_cache_hits']}, misses "
        f"{result['persistent_cache_misses']}) ===", flush=True,
    )
    return result


def _train_pair(label: str, workdir: str, **kw: Any) -> Dict[str, Any]:
    sub = os.path.join(workdir, label)
    os.makedirs(sub)
    first = _run_child(f"{label}_first", "train_first", sub, **kw)
    resume = _run_child(f"{label}_resume", "train_resume", sub, **kw)
    shutil.rmtree(sub)  # the checkpoints are gigabytes
    return {"first": first, "resume": resume}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--phase", help=argparse.SUPPRESS)
    parser.add_argument("--workdir", help=argparse.SUPPRESS)
    parser.add_argument("--out", help=argparse.SUPPRESS)
    parser.add_argument("--layers", type=int, help=argparse.SUPPRESS)
    parser.add_argument("--batch-size", type=int, help=argparse.SUPPRESS)
    parser.add_argument("--mesh-flags", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, PACKAGE)):
        print(
            f"chip_smoke: {PACKAGE}/ is not beside this file — run it from "
            "a checkout of the repository", file=sys.stderr,
        )
        return EXIT_NO_REPO
    if args.phase:
        return _child_main(args)

    t_start = time.time()
    workdir = tempfile.mkdtemp(prefix="chip_smoke-")  # outputs only
    try:
        kernels = _run_child("kernels", "kernels", workdir)
        device = kernels["device"]
        print(
            f"platform: {device['platform']}\n"
            f"device_kind: {device['kind']}\n"
            f"device_count: {device['count']}\n"
            f"hbm_bytes_limit: {device['hbm_bytes_limit']}\n"
            f"jax: {device['jax']}\nlibtpu: {device['libtpu']}", flush=True,
        )
        serve_cold = _run_child("serve_cold", "serve", workdir)
        serve_warm = _run_child("serve_warm", "serve", workdir)
        for name, run in serve_cold["runs"].items():
            again = serve_warm["runs"][name]["token_digest"]
            if run["token_digest"] != again:
                print(
                    f"chip_smoke: FAILED serve[{name}]: greedy tokens "
                    "differ between two runs of the same seed",
                    file=sys.stderr,
                )
                return EXIT_FAILED
        # the full job, data-parallel over every chip the host has
        train = {"full_job": _train_pair("train", workdir)}
        if device["count"] >= 4:
            cut = dict(layers=FULL.multichip_layers,
                       batch_size=FULL.multichip_batch_size)
            train["data_x_fsdp_2x2"] = _train_pair(
                "train_fsdp2", workdir, mesh_flags="--fsdp 2", **cut
            )
            train["tensor_2"] = _train_pair(
                "train_tensor2", workdir, mesh_flags="--tensor 2", **cut
            )
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    children = {
        "kernels": kernels, "serve_cold": serve_cold,
        "serve_warm": serve_warm,
        "train_cold": train["full_job"]["first"],
        "train_warm": train["full_job"]["resume"],
    }
    summary = {
        "kernels": kernels["kernels"],
        "serve": serve_cold["runs"],
        "train": {
            name: {
                "checkpoint_step": pair["first"]["checkpoint_step"],
                **{k: pair["resume"][k] for k in
                   ("steps", "restored_from_step", "epoch_losses")},
            }
            for name, pair in train.items()
        },
        # set-up facts, not metrics: how long the smoke takes to start,
        # cold and on the second invocation, and what it compiled
        "setup": {
            "compile_cache_dir": kernels["cache_dir"],
            "compile_cache_warm_at_start":
                kernels["cache_entries_before"] > 0,
            "total_wall_s": round(time.time() - t_start, 1),
            "children": {
                name: {k: child[k] for k in (
                    "process_wall_s", "compile_wall_s", "compile_requests",
                    "persistent_cache_hits", "persistent_cache_misses",
                )}
                for name, child in children.items()
            },
        },
        "claim": None,
    }
    print("\n=== chip_smoke summary ===")
    print(json.dumps(summary, indent=1))
    print(json.dumps({
        "ok": True,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"]},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
