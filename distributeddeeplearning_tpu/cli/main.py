"""``ddlt`` — the control-plane CLI.

The TPU-native replacement for the reference's invoke task tree: the root
namespace (``{{proj}}/tasks.py:27-225`` — setup/login/delete/tensorboard/
runs/experiments), the per-workload submit modules
(``tensorflow_imagenet.py:110-176`` etc. — ``submit.{local,remote}.
{synthetic,images,tfrecords}``), and the storage scripts
(``scripts/{storage,image,tfrecords}.py``).  Verb-for-verb, on argparse
subcommands (no third-party task runner):

    ddlt setup                      inv setup
    ddlt login / select-project     inv login / select-subscription
    ddlt imagenet submit local synthetic
                                    inv tf-imagenet.submit.local.synthetic
    ddlt benchmark submit remote synthetic
                                    inv pytorch-benchmark.submit.remote.synthetic
    ddlt storage create-bucket      inv storage.create-premium-storage (+key)
    ddlt storage upload-images      inv storage.image.upload-data
    ddlt storage generate-tfrecords inv storage.tfrecords.generate-tf-records
    ddlt tensorboard / runs / experiments / delete / tpu …   (same roles)

Unknown ``--flag value`` pairs after a submit verb pass through to the
workload's ``main`` (the reference's ``script_params`` dict).  ``--dry-run``
prints every cloud/launcher command instead of executing — the operator can
copy/paste, and tests assert the composed command lines.
"""

from __future__ import annotations

import argparse
import logging
import sys
from typing import Any, Dict, List, Optional

from distributeddeeplearning_tpu.config import load_config
from distributeddeeplearning_tpu.version import __version__

logger = logging.getLogger("ddlt.cli")

DATA_FORMATS = ("synthetic", "images", "tfrecords")


def _data_params(data_format: str, mode: str) -> Dict[str, Any]:
    """Default script params per input mode — parity with the reference's
    submit modules (``tensorflow_imagenet.py:69-70,96-97,124-125,151-152``).

    Local mode resolves ``{datastore}`` to DATA_DIR, remote to the bucket
    (``Submitter._resolve_params``); the templated shape is identical.
    """
    if data_format == "synthetic":
        return {"data_format": "synthetic"}
    if data_format == "images":
        return {
            "data_format": "images",
            "training_data_path": "{datastore}/images/train",
            "validation_data_path": "{datastore}/images/validation",
        }
    if data_format == "tfrecords":
        return {
            "data_format": "tfrecords",
            "training_data_path": "{datastore}/tfrecords",
            "validation_data_path": "{datastore}/tfrecords",
        }
    raise ValueError(f"unknown data format {data_format!r}")


def _add_submit_tree(sub, workload: str, formats=DATA_FORMATS) -> None:
    """Attach ``<workload> submit {local,remote} [<format>]`` verbs."""
    wl = sub.add_parser(workload, help=f"{workload} workload")
    wl_sub = wl.add_subparsers(dest=f"{workload}_command", required=True)
    submit = wl_sub.add_parser("submit", help="Submit a training run")
    submit_sub = submit.add_subparsers(dest="mode", required=True)
    for mode in ("local", "remote"):
        mode_p = submit_sub.add_parser(
            mode,
            help=f"{mode} run"
            + (" (single-host debug path)" if mode == "local" else " (TPU pod)"),
        )
        if formats:
            fmt_sub = mode_p.add_subparsers(dest="data_format", required=True)
            for fmt in formats:
                fmt_p = fmt_sub.add_parser(fmt, help=f"{fmt} input data")
                fmt_p.add_argument("--experiment", default=None)
                if mode == "remote":
                    fmt_p.add_argument(
                        "--max-retries", type=int, default=None,
                        help="Recreate the pod and resubmit on preemption "
                        "(default: MAX_RETRIES setting, 0)",
                    )
        else:
            mode_p.add_argument("--experiment", default=None)
            if mode == "remote":
                mode_p.add_argument("--max-retries", type=int, default=None)


def _global_flags(parser, suppress: bool = False) -> None:
    """--env-file / --dry-run, accepted both before and after the verb.

    Subparsers get SUPPRESS defaults so a flag given before the verb is not
    clobbered by the subparser's default when omitted after it.
    """
    parser.add_argument(
        "--env-file",
        default=argparse.SUPPRESS if suppress else None,
        help="Path to .env (default: ./.env)",
    )
    parser.add_argument(
        "--dry-run",
        action="store_true",
        default=argparse.SUPPRESS if suppress else False,
        help="Print cloud/launcher commands instead of executing them",
    )


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="ddlt",
        description="TPU-native distributed deep learning control plane.",
    )
    _global_flags(parser)
    sub = parser.add_subparsers(dest="command")

    sub.add_parser("version", help="Print framework version")

    config_p = sub.add_parser("config", help="Configuration inspection")
    config_sub = config_p.add_subparsers(dest="config_command", required=True)
    config_sub.add_parser("show", help="Print resolved configuration")
    set_p = config_sub.add_parser("set", help="Persist KEY=VALUE into .env")
    set_p.add_argument("key")
    set_p.add_argument("value")

    sub.add_parser("login", help="Authenticate gcloud (inv login parity)")
    proj_p = sub.add_parser(
        "select-project", help="Select GCP project, persist to .env"
    )
    proj_p.add_argument("--project", default=None)

    setup_p = sub.add_parser(
        "setup", help="Provision storage + prepare and upload data (inv setup)"
    )
    setup_p.add_argument("--skip-imagenet", action="store_true")
    setup_p.add_argument("--skip-tfrecords", action="store_true")
    setup_p.add_argument("--train-tar", default=None)
    setup_p.add_argument("--val-tar", default=None)
    setup_p.add_argument("--val-map", default=None)
    setup_p.add_argument("--force", action="store_true",
                         help="Convert partial data sets")

    delete_p = sub.add_parser(
        "delete", help="Delete the TPU pod (and optionally the bucket)"
    )
    delete_p.add_argument("--storage", action="store_true",
                          help="Also delete the GCS bucket")

    tpu_p = sub.add_parser("tpu", help="TPU pod lifecycle")
    tpu_sub = tpu_p.add_subparsers(dest="tpu_command", required=True)
    tpu_sub.add_parser("create", help="Idempotent get-or-create")
    tpu_sub.add_parser("delete", help="Delete the pod")
    tpu_sub.add_parser("status", help="Describe the pod")
    tpu_sub.add_parser("list", help="List pods in the zone")
    q_p = tpu_sub.add_parser(
        "queue", help="File a queued-resource request for the pod "
        "(how v5e+ capacity is obtained in practice)"
    )
    q_p.add_argument("--request-id", default=None)
    q_kind = q_p.add_mutually_exclusive_group()
    q_kind.add_argument("--spot", action="store_true")
    q_kind.add_argument("--reserved", action="store_true")
    q_p.add_argument("--valid-until", default=None,
                     help="e.g. 6h — auto-expire an unfulfilled request")
    qs_p = tpu_sub.add_parser(
        "queue-status", help="Queued-resource request state"
    )
    qs_p.add_argument("--request-id", default=None)
    qd_p = tpu_sub.add_parser(
        "queue-delete", help="Cancel/release the queued-resource request"
    )
    qd_p.add_argument("--request-id", default=None)
    qd_p.add_argument(
        "--force", action="store_true",
        help="Required when the request is ACTIVE (tears down its live node)",
    )
    ssh_p = tpu_sub.add_parser("ssh", help="Run a command on pod workers")
    ssh_p.add_argument("--worker", default="all")
    ssh_p.add_argument("cmd", help="Shell command to run")
    boot_p = tpu_sub.add_parser(
        "bootstrap", help="Copy the framework to all workers and install it"
    )
    boot_p.add_argument("--project-dir", default=".")

    st_p = sub.add_parser("storage", help="GCS data-plane tasks")
    st_sub = st_p.add_subparsers(dest="storage_command", required=True)
    st_sub.add_parser("create-bucket", help="Idempotent bucket create + .env write-back")
    for verb, help_text in (
        ("upload-images", "Upload train/validation image trees"),
        ("download-images", "Download train/validation image trees"),
        ("upload-tfrecords", "Upload TFRecord shards"),
        ("download-tfrecords", "Download TFRecord shards"),
    ):
        v = st_sub.add_parser(verb, help=help_text)
        v.add_argument("--data-dir", default=None)
    prep_p = st_sub.add_parser(
        "prepare-imagenet", help="Verify, extract, reorganize the ImageNet tars"
    )
    prep_p.add_argument("--train-tar", required=True)
    prep_p.add_argument("--val-tar", required=True)
    prep_p.add_argument(
        "--val-map", default=None,
        help="filename<->wnid CSV; omitted = derive it from the "
        "ILSVRC2012 devkit tar next to --val-tar (checksum-verified)",
    )
    prep_p.add_argument("--target-dir", default=None)
    prep_p.add_argument("--no-checksum", action="store_true")
    bc_p = st_sub.add_parser(
        "build-cache",
        help="Decode TFRecord shards once into the raw uint8 cache "
        "(data/raw_cache.py) used by --input_pipeline raw",
    )
    bc_p.add_argument("--data-dir", required=True,
                      help="TFRecord shard directory")
    bc_p.add_argument("--split", default="train",
                      choices=("train", "validation"))
    bc_p.add_argument("--image-size", type=int, default=224)
    bc_p.add_argument("--cache-dir", default=None,
                      help="default: <data-dir>/raw-cache-<split>-<size>"
                      "[-shardIofN with --shard-count] — the exact dir a "
                      "run with the same shard settings will look for")
    bc_p.add_argument(
        "--shard-count", type=int, default=1,
        help="total hosts of the multi-host run this cache is for; "
        "multi-host imagenet runs read per-host '-shardIofN'-suffixed "
        "cache dirs, so pre-build one per host (default 1: single-host, "
        "unsuffixed)",
    )
    bc_p.add_argument(
        "--shard-index", type=int, default=0,
        help="which host's slice to build (0-based, with --shard-count)",
    )
    vm_p = st_sub.add_parser(
        "val-maps",
        help="Derive imagenet_val_maps.csv from the ILSVRC2012 devkit tar "
        "(sha256-verified against the canonical map)",
    )
    vm_p.add_argument("--devkit", required=True)
    vm_p.add_argument("--out", default="imagenet_val_maps.csv")
    vm_p.add_argument(
        "--no-verify", action="store_true",
        help="write even if the sha256 does not match the canonical map",
    )
    ci_p = st_sub.add_parser(
        "class-index",
        help="Derive the wnid->class mapping from the train tree; "
        "optionally verify a canonical imagenet_class_index.json against it",
    )
    ci_p.add_argument("--image-dir", default=None)
    ci_p.add_argument("--output", default=None,
                      help="Where to write imagenet_nounid_to_class.json")
    ci_p.add_argument("--verify", nargs="?", default=None, const="shipped",
                      help="Canonical keras-style class index JSON to check "
                      "(no value = the in-repo canonical file)")
    ci_p.add_argument("--label-offset", type=int, default=1,
                      help="1 (default) = this framework's 1001-class "
                      "background-head labels; 0 = the reference's 0-based "
                      "imagenet_nounid_to_class.json format")
    gen_p = st_sub.add_parser(
        "generate-tfrecords", help="Convert image trees to TFRecord shards (gated)"
    )
    gen_p.add_argument("--image-dir", default=None)
    gen_p.add_argument("--output-dir", default=None)
    gen_p.add_argument("--force", action="store_true")
    gen_p.add_argument("--train-shards", type=int, default=None)
    gen_p.add_argument("--validation-shards", type=int, default=None)

    _add_submit_tree(sub, "imagenet")
    _add_submit_tree(sub, "bert", formats=("synthetic", "tfrecords"))
    _add_submit_tree(sub, "transformer", formats=("synthetic",))
    _add_submit_tree(sub, "benchmark", formats=("synthetic",))
    _add_submit_tree(sub, "experiment", formats=())

    train_p = sub.add_parser(
        "train",
        help="Run a workload IN-PROCESS under the restart supervisor "
        "(train/resilience.py): on preemption, anomaly abort or data-stream "
        "death the workload is re-entered and resumes from its latest "
        "checkpoint, up to --max-restarts times.  Unknown --flags pass "
        "through to the workload main (same contract as the submit verbs).",
    )
    train_p.add_argument(
        "train_workload",
        metavar="workload",
        choices=("imagenet", "bert", "transformer", "benchmark", "experiment"),
        help="workload module to supervise",
    )
    train_p.add_argument(
        "--max-restarts", type=int, default=0,
        help="in-process restarts after a restartable failure (preemption, "
        "anomaly abort, data-stream death); pass --save_filepath so the "
        "restart actually resumes instead of starting over",
    )
    train_p.add_argument(
        "--faults", default=None,
        help="fault-injection spec (overrides the DDLT_FAULTS env var), "
        'e.g. "nan_loss@12,preempt@50" — see README "Fault tolerance"',
    )
    train_p.add_argument(
        "--comm-overlap", action="store_true", default=None,
        help="explicit gradient comms (parallel/comms.py): bucketed "
        "reduce-scatter issued per microbatch inside the accumulation "
        "scan, overlapping wire time with backward compute, instead of "
        "the implicit post-backward GSPMD allreduce",
    )
    train_p.add_argument(
        "--bucket-mb", type=float, default=None,
        help="gradient bucket size in MB for --comm-overlap (default 4)",
    )
    train_p.add_argument(
        "--comm-dtype", default=None, choices=("f32", "bf16"),
        help="wire dtype for the gradient reduce-scatter; bf16 halves "
        "bytes on the wire with per-bucket error-feedback residuals "
        "(carried in the train state and checkpointed)",
    )
    train_p.add_argument(
        "--weight-update-sharding", action="store_true", default=None,
        help="ZeRO-style distributed optimizer for --comm-overlap: each "
        "chip updates its 1/N gradient shard and all-gathers params, "
        "cutting optimizer FLOPs and momentum/Adam-moment HBM by N",
    )

    serve_p = sub.add_parser(
        "serve",
        help="KV-cached autoregressive inference with continuous batching "
        "(serve/): prompts from stdin/--prompt-file as token-id lines, or "
        "--synthetic",
    )
    src = serve_p.add_mutually_exclusive_group()
    src.add_argument(
        "--prompt-file", default=None,
        help="file of prompts, one per line as whitespace-separated token "
        "ids ('-' = stdin; default: stdin when piped)",
    )
    src.add_argument(
        "--synthetic", action="store_true",
        help="generate --requests random prompts (benchmark mode; stats "
        "JSON goes to stdout)",
    )
    serve_p.add_argument("--requests", type=int, default=12,
                         help="synthetic request count (keep > --batch-slots "
                         "so continuous batching reuses slots)")
    serve_p.add_argument("--prompt-len", type=int, default=16,
                         help="max synthetic prompt length")
    serve_p.add_argument("--shared-prefix-len", type=int, default=0,
                         help="prepend the same random prefix of this many "
                         "tokens to every synthetic prompt (on top of "
                         "--prompt-len) — the system-prompt workload the "
                         "paged layout's prefix cache serves from shared "
                         "pages")
    serve_p.add_argument("--batch-slots", type=int, default=4,
                         help="KV-cache slots (the decode batch width)")
    serve_p.add_argument("--max-new-tokens", type=int, default=32)
    serve_p.add_argument("--max-seq", type=int, default=None,
                         help="cache length per slot (default: prompt cap + "
                         "--max-new-tokens)")
    serve_p.add_argument("--temperature", type=float, default=0.0,
                         help="0 = greedy (deterministic)")
    serve_p.add_argument("--top-k", type=int, default=None)
    serve_p.add_argument("--eos-id", type=int, default=None,
                         help="token id that ends a sequence early")
    serve_p.add_argument("--seed", type=int, default=0,
                         help="sampling RNG seed (step-folded per draw)")
    serve_p.add_argument("--checkpoint-dir", default=None,
                         help="orbax checkpoint dir (train/checkpoint.py); "
                         "restores the latest step's params")
    serve_p.add_argument("--model-config", default=None, metavar="JSON",
                         help="serve a decoder that mixes window and full "
                         "attention layers, or gated short-convolution and "
                         "attention layers, with sparse experts, or "
                         "state-space (Mamba) and attention layers "
                         "(models/hybrid_moe_transformer.py), at the sizes "
                         "of this configuration file under the model's "
                         "published keys (benchmarks/configs/"
                         "mimo-v2-flash.json, lfm2-8b-a1b.json, "
                         "trinity-mini.json and jamba2-3b.json are four), "
                         "seeded weights; needs "
                         "--kv-layout paged --no-prefix-cache, and refuses "
                         "the int8 pool, the host tier, --speculative and "
                         "--replicas > 1")
    serve_p.add_argument("--prefill-attention", default="flash",
                         choices=("flash", "dense"),
                         help="prompt-pass attention (decode is always "
                         "dense against the cache; paged layout prefills "
                         "through its chunk program instead)")
    serve_p.add_argument("--kv-layout", default="dense",
                         choices=("dense", "paged"),
                         help="KV-cache layout: dense reserves max_seq per "
                         "slot; paged allocates fixed-size pages by actual "
                         "tokens, shares identical prompt-prefix pages, "
                         "and prefills long prompts in chunks interleaved "
                         "with decode steps")
    serve_p.add_argument("--page-size", type=int, default=64,
                         help="tokens per KV page (--kv-layout paged)")
    serve_p.add_argument("--kv-pages", type=int, default=None,
                         help="page-pool size (--kv-layout paged; default: "
                         "dense-capacity parity, batch_slots x "
                         "ceil(max_seq/page_size) — set LOWER to trade "
                         "admission concurrency for HBM)")
    serve_p.add_argument("--prefill-chunk", type=int, default=64,
                         help="prompt tokens prefilled per interleaved "
                         "chunk (--kv-layout paged): caps how long one "
                         "admission can stall in-flight decode steps")
    serve_p.add_argument("--no-prefix-cache", action="store_true",
                         help="disable shared-prefix page reuse "
                         "(--kv-layout paged)")
    serve_p.add_argument("--decode-kernel", default="auto",
                         choices=("auto", "flash", "gather"),
                         help="how decode attention consumes the KV "
                         "cache (ops/flash_decode.py): 'flash' streams "
                         "cache pages through the paged flash-decode "
                         "kernel (Pallas on TPU with in-tile int8 "
                         "dequant — f32 history never materializes in "
                         "HBM; a fused-XLA twin elsewhere, bitwise "
                         "identical to gather for f32 caches); 'gather' "
                         "is the legacy block-table-gather read; "
                         "'auto' (default) = flash")
    serve_p.add_argument("--quantize-kv", default=None, choices=("int8",),
                         help="store the KV cache int8 with per-position-"
                         "per-head f32 scales (quant/): ~3.2x smaller KV "
                         "HBM, dequant fused into the decode attention; "
                         "works with both --kv-layout values")
    serve_p.add_argument("--quantize-weights", default=None,
                         choices=("int8",),
                         help="post-training int8 weight quantization of "
                         "the matmul weights (per-output-channel absmax "
                         "scales, int8 dot_general compute); embeddings/"
                         "layer norms stay f32")
    serve_p.add_argument("--calib-prompts", type=int, default=8,
                         help="synthetic calibration prompts run through "
                         "the f32 and quantized model before serving "
                         "(--quantize-weights): prints logit MAE + greedy "
                         "agreement to stderr; 0 = quantize blind")
    serve_p.add_argument("--speculative", action="store_true",
                         help="speculative decoding (spec/): a cheap "
                         "drafter proposes --draft-tokens greedy tokens "
                         "per slot and the full model verifies all K+1 "
                         "positions in one batched call — greedy output "
                         "stays bit-identical to non-speculative decode. "
                         "Greedy-only (temperature 0) and f32 KV cache "
                         "only; single replica")
    serve_p.add_argument("--draft-tokens", type=int, default=4,
                         help="draft tokens K per speculative step (each "
                         "step commits 1..K+1 tokens per slot)")
    serve_p.add_argument("--draft-layers", type=int, default=None,
                         help="layers of the truncated self-draft drafter "
                         "(first M layers of the shared stack + the "
                         "shared head; default: half the stack).  "
                         "Ignored with --draft-weights int8")
    serve_p.add_argument("--draft-weights", default=None,
                         choices=("int8",),
                         help="draft with the full-depth int8-weight "
                         "model instead of the truncated stack (the f32 "
                         "model still verifies, so output is unchanged); "
                         "with --checkpoint-dir the drafter restores via "
                         "restore_params(quantize_weights='int8')")
    serve_p.add_argument("--replicas", type=int, default=1,
                         help="engine replica WORKER PROCESSES (serve/"
                         "fleet.py): >1 runs the supervised fleet — a "
                         "router load-balances requests, health-checks "
                         "replicas by heartbeat, restarts dead ones and "
                         "fails in-flight requests over to survivors "
                         "(greedy output stays bit-identical)")
    serve_p.add_argument("--max-restarts", type=int, default=1,
                         help="restarts each dead replica gets before it "
                         "stays down (--replicas > 1)")
    serve_p.add_argument("--max-redeliveries", type=int, default=2,
                         help="failover retries per request before it "
                         "finishes 'error' (at-most-K redelivery)")
    serve_p.add_argument("--priority-classes", default=None,
                         help="comma-separated tenant priority classes, "
                         "highest first (default 'premium,standard,"
                         "best_effort'): higher classes dequeue first "
                         "and may preempt lower-class decodes "
                         "losslessly under slot/memory pressure")
    serve_p.add_argument("--shed-policy", default="block",
                         help="admission behavior under memory pressure: "
                         "'block' (default) queues everything; 'shed' "
                         "fails lowest-class requests fast with finish_"
                         "reason 'shed' + a retry_after_s hint")
    serve_p.add_argument("--preempt-budget", type=int, default=2,
                         help="times one request may be preempted (and "
                         "losslessly resumed) before it finishes "
                         "terminal 'preempted' — bounds starvation")
    serve_p.add_argument("--tenant-slo", action="append", default=None,
                         metavar="CLASS:SPEC",
                         help="per-class SLO, repeatable (--replicas > 1)"
                         ": e.g. --tenant-slo premium:ttft_p99_s=2.0,"
                         "max_error_rate=0 --tenant-slo best_effort:"
                         "max_lost_requests=0; evaluated over the "
                         "per-class bucket-merged fleet metrics, exit 1 "
                         "on violation")
    serve_p.add_argument("--request-deadline-s", type=float, default=None,
                         help="per-request deadline: past it a request "
                         "finishes 'deadline' (queued: unstarted; "
                         "decoding: with its partial tokens)")
    serve_p.add_argument("--watchdog-deadline-s", type=float, default=None,
                         help="scheduler-loop watchdog (train/resilience."
                         "StepWatchdog): no loop progress for this long "
                         "dumps stacks and exits 70 so a supervisor "
                         "restarts the worker")
    serve_p.add_argument("--heartbeat-timeout-s", type=float, default=None,
                         help="router-side staleness bound on replica "
                         "heartbeats (--replicas > 1): a silent replica "
                         "with work in flight is killed and its requests "
                         "failed over.  Size it ABOVE the worst-case jit "
                         "compile (a blocking compile gaps the heartbeat "
                         "stream); for finer hang detection use "
                         "--watchdog-deadline-s, which runs inside the "
                         "worker and excludes first-step compiles")
    serve_p.add_argument("--report", default=None,
                         help="also write the stats JSON here "
                         "(e.g. SERVE_r06.json)")
    serve_p.add_argument("--trace-dir", default=None,
                         help="enable the obs tracer + jax.profiler for "
                         "this run and write the merged host+device "
                         "Chrome trace (merged.trace.json — open in "
                         "chrome://tracing or Perfetto) under this dir")
    for flag, default in (("--num-layers", 2), ("--d-model", 64),
                          ("--d-ff", 128), ("--vocab-size", 257)):
        serve_p.add_argument(flag, type=int, default=default,
                             help="model dim (ignored with --checkpoint-dir"
                             " — dims come from the restored params)")
    serve_p.add_argument(
        "--num-heads", type=int, default=None,
        help="attention heads (default 4).  REQUIRED with "
        "--checkpoint-dir: the head count is not derivable from the "
        "saved qkv shapes, and a wrong-but-dividing value generates "
        "garbage silently",
    )

    obs_p = sub.add_parser(
        "obs",
        help="Profile a short train or serve run with the obs stack "
        "(obs/): host spans + jax.profiler merged onto one Chrome-trace "
        "timeline, metrics-registry snapshot, summary JSON to stdout",
    )
    obs_sub = obs_p.add_subparsers(dest="obs_command", required=True)
    obs_serve = obs_sub.add_parser(
        "serve", help="profile a synthetic serving run (paged engine)"
    )
    obs_serve.add_argument("--requests", type=int, default=8)
    obs_serve.add_argument("--batch-slots", type=int, default=4)
    obs_serve.add_argument("--max-new-tokens", type=int, default=8)
    obs_serve.add_argument("--prompt-len", type=int, default=16)
    obs_serve.add_argument("--quantize-kv", default=None, choices=("int8",),
                           help="profile the int8-KV engine instead of f32")
    obs_train = obs_sub.add_parser(
        "train", help="profile a short synthetic training fit"
    )
    obs_train.add_argument("--steps", type=int, default=8)
    obs_train.add_argument("--batch-size", type=int, default=16)
    obs_fleet = obs_sub.add_parser(
        "fleet",
        help="fleet-scale observability smoke: a multi-replica chaos "
        "run with distributed tracing (per-worker shards merged onto "
        "the router clock -> fleet.trace.json), bucket-merged fleet "
        "TTFT/TPOT percentiles, flight-recorder dumps, and the SLO "
        "verdict",
    )
    obs_fleet.add_argument("--replicas", type=int, default=2)
    obs_fleet.add_argument("--requests", type=int, default=12)
    obs_fleet.add_argument("--batch-slots", type=int, default=2)
    obs_fleet.add_argument("--max-new-tokens", type=int, default=8)
    obs_fleet.add_argument("--prompt-len", type=int, default=10)
    obs_fleet.add_argument(
        "--faults", default="replica_death@3,decode_stall@5:secs=0.2",
        help="serve-side DDLT_FAULTS schedule dealt across the fleet "
        "(default injects one death + one stall so the merged timeline "
        "shows a real failover)",
    )
    obs_fleet.add_argument(
        "--slo", default="max_error_rate=0,max_lost_requests=0",
        help="declarative SLO spec evaluated over the merged fleet "
        "metrics, e.g. 'ttft_p99_s=2.0,tpot_p99_s=0.5,"
        "max_error_rate=0,max_lost_requests=0'; exit 1 on violation",
    )
    obs_fleet.add_argument(
        "--slo-per-tenant", action="append", default=None,
        metavar="CLASS:SPEC",
        help="per-priority-class SLO, repeatable: e.g. --slo-per-tenant "
        "premium:ttft_p99_s=2.0,max_error_rate=0 --slo-per-tenant "
        "best_effort:max_lost_requests=0; each class's spec is "
        "evaluated over that class's bucket-merged fleet latency; "
        "exit 1 on any violation",
    )
    for p in (obs_serve, obs_train, obs_fleet):
        p.add_argument(
            "--trace-dir", default="ddlt-obs",
            help="output dir: device trace + merged.trace.json + "
            "obs-metrics.jsonl (default ./ddlt-obs)",
        )
    obs_attrib = obs_sub.add_parser(
        "attrib",
        help="per-program cost/HBM attribution (obs/attrib.py): build "
        "tiny dense+paged engines (and a speculative decoder) on the CPU "
        "(or the platform JAX_PLATFORMS names), serve synthetic traffic, "
        "then report every "
        "compiled program's cost_analysis flops/bytes + memory_analysis "
        "residency, the HBM ledger's owner totals reconciled against "
        "the process's live device bytes, and achieved-vs-roofline per "
        "program; --check exits nonzero when any attribution gate "
        "fails (the make obs-gate half that needs jax)",
    )
    obs_attrib.add_argument(
        "--check", action="store_true",
        help="gate mode: print the gate verdicts only, exit 1 on any "
        "failure (programs unresolvable, owner totals drifting from "
        "live bytes, unaccounted-HBM residual past its limit)",
    )
    obs_attrib.add_argument(
        "--json", action="store_true", help="print the full report JSON",
    )
    obs_attrib.add_argument(
        "--report", default=None,
        help="also write the full report JSON to this path",
    )
    obs_attrib.add_argument(
        "--no-spec", action="store_true",
        help="skip the speculative-decoder programs (faster smoke)",
    )
    obs_history = obs_sub.add_parser(
        "history",
        help="perf-trajectory tracker (obs/history.py): parse every "
        "committed <KIND>_r{NN}.json through the schema validators into "
        "one metric timeline, print per-series sparkline deltas; "
        "--gate exits 1 when a tracked metric regressed past its "
        "tolerance between the two newest revisions (make perf-history)",
    )
    obs_history.add_argument(
        "--root", default=".",
        help="directory holding the committed *_r*.json artifacts "
        "(default: the current directory)",
    )
    obs_history.add_argument(
        "--json", action="store_true",
        help="machine-readable trajectory digest on stdout",
    )
    obs_history.add_argument(
        "--gate", action="store_true",
        help="fail (rc 1) on any tracked metric regressing past its "
        "per-metric tolerance (obs/history.TOLERANCES)",
    )

    inter_p = sub.add_parser(
        "interactive",
        help="Open an interactive shell on a pod worker (inv interactive), "
        "or --repl for a local Python session with the SDK objects preloaded",
    )
    inter_p.add_argument("--worker", default="0")
    inter_p.add_argument(
        "--repl", action="store_true",
        help="operator-side IPython/Python REPL with cfg, runner, registry, "
        "pod, submitter and storage in scope (the reference's `inv "
        "interactive` opened exactly this against its SDK)",
    )

    comp_p = sub.add_parser(
        "completion",
        help="Print a shell completion script (install: ddlt completion "
        "bash > /etc/bash_completion.d/ddlt)",
    )
    comp_p.add_argument("shell", choices=("bash", "zsh"))

    tb_p = sub.add_parser("tensorboard", help="TensorBoard over registry runs")
    tb_p.add_argument("--experiment", default=None)
    tb_p.add_argument("--run", default=None)
    tb_p.add_argument("--port", type=int, default=6006)

    runs_p = sub.add_parser("runs", help="List last N runs of an experiment")
    runs_p.add_argument("--experiment", default=None)
    runs_p.add_argument("--last", type=int, default=10)
    runs_p.add_argument(
        "--status", default=None,
        choices=("queued", "running", "completed", "failed"),
        help="Only show runs in this state (e.g. --status running)",
    )
    runs_p.add_argument(
        "--run", default=None,
        help="Show one run: status + log tail + per-epoch metric rows",
    )
    runs_p.add_argument(
        "--tail", type=int, default=20,
        help="With --run: how many log lines to show (0 = none)",
    )
    runs_p.add_argument(
        "--refresh", action="store_true",
        help="With --run: probe the pod and flip a stale 'running' status",
    )
    runs_p.add_argument(
        "--metrics-only", action="store_true",
        help="With --run: print only the metrics JSONL rows (old behavior)",
    )

    lint_p = sub.add_parser(
        "lint",
        help="Static analysis over the hot-loop / program invariants "
        "(analysis/): AST host-sync checker over the hot-region registry "
        "+ jaxpr/HLO program audits (donation, collective signature, int8 "
        "dtype audit, sharding coverage, fault coverage).  Runs on an "
        "8-device virtual CPU pod, never on a chip.  Exits non-zero on "
        "any unwaived finding.",
    )
    lint_p.add_argument(
        "--no-programs", action="store_true",
        help="AST layer only — skip the jaxpr/HLO program audits "
        "(no backend init or tracing; seconds instead of tens of "
        "seconds)",
    )
    lint_p.add_argument(
        "--json", action="store_true",
        help="machine-readable findings (list of objects) on stdout",
    )

    sub.add_parser("experiments", help="List experiments in the run registry")

    new_p = sub.add_parser("new", help="Generate a new project scaffold")
    new_p.add_argument("name")
    new_p.add_argument("--output-dir", default=".")
    new_p.add_argument("--gcp-project", default="")
    new_p.add_argument("--gcp-zone", default=None)
    new_p.add_argument("--tpu-type", default=None)
    new_p.add_argument("--gcs-bucket", default="")

    _attach_globals_recursively(parser)
    return parser


def _attach_globals_recursively(parser: argparse.ArgumentParser) -> None:
    """Accept --env-file/--dry-run after any verb as well as before it."""
    for action in parser._actions:
        if isinstance(action, argparse._SubParsersAction):
            for child in action.choices.values():
                _global_flags(child, suppress=True)
                _attach_globals_recursively(child)


def _control(args):
    from distributeddeeplearning_tpu.control import CommandRunner
    from distributeddeeplearning_tpu.control.runs import RunRegistry

    cfg = load_config(args.env_file)
    runner = CommandRunner(dry_run=args.dry_run)
    registry = RunRegistry(cfg.get("RUNS_DIR", "runs") or "runs")
    return cfg, runner, registry


def _submit(args, workload: str, extra: List[str]) -> int:
    from distributeddeeplearning_tpu.control.submit import Submitter
    from distributeddeeplearning_tpu.workloads._runner import parse_flags

    cfg, runner, registry = _control(args)
    params: Dict[str, Any] = {}
    if getattr(args, "data_format", None):
        params.update(_data_params(args.data_format, args.mode))
    params.update(parse_flags(extra))
    submitter = Submitter(cfg, runner, registry)
    if args.mode == "local":
        run = submitter.submit_local(
            workload, params, experiment=args.experiment
        )
    else:
        run = submitter.submit_remote(
            workload, params, experiment=args.experiment,
            max_retries=getattr(args, "max_retries", None),
        )
    print(f"run {run.experiment}/{run.run_id}: {run.status}")
    return 0 if run.status == "completed" or args.dry_run else 1


def _repl(cfg, runner, registry) -> int:
    """Operator-side REPL with the control-plane SDK preloaded — the role of
    the reference's ``inv interactive`` (IPython with the AML workspace
    objects in scope, ``tasks.py:84-87``).  IPython when available, stdlib
    ``code.interact`` otherwise."""
    from distributeddeeplearning_tpu.control.storage import GcsStorage
    from distributeddeeplearning_tpu.control.submit import Submitter
    from distributeddeeplearning_tpu.control.tpu import pod_from_settings

    namespace = {
        "cfg": cfg,
        "runner": runner,
        "registry": registry,
        "pod": pod_from_settings(cfg, runner),
        "submitter": Submitter(cfg, runner, registry),
    }
    if cfg.get("GCS_BUCKET"):
        namespace["storage"] = GcsStorage(runner, bucket=cfg.get("GCS_BUCKET"))
    banner = (
        "ddlt interactive REPL — preloaded: "
        + ", ".join(sorted(namespace))
        + "\n(e.g. pod.state(), submitter.poll_run(...), storage.exists())"
    )
    try:
        from IPython import start_ipython
        from traitlets.config import Config

        # display_banner is a Bool trait; the banner TEXT goes through
        # TerminalInteractiveShell.banner1.
        config = Config()
        config.TerminalInteractiveShell.banner1 = banner + "\n"
        start_ipython(argv=[], user_ns=namespace, config=config)
    except ImportError:
        import code

        code.interact(banner=banner, local=namespace)
    return 0


def _emit_completion(parser, shell: str) -> int:
    """Print a bash/zsh completion script for the ``ddlt`` verb tree.

    The reference bakes invoke's bash completion into its control image
    (``control/Docker/bash.completion`` installed by
    ``control/Docker/dockerfile``); here the script is GENERATED from the
    live argparse tree (verbs, sub-verbs and flags are introspected, so it
    never drifts from the CLI), and the control image installs it with
    ``ddlt completion bash > /etc/bash_completion.d/ddlt``.
    """

    def subactions(p):
        for action in p._actions:
            if isinstance(action, argparse._SubParsersAction):
                return action.choices
        return {}

    def flags(p):
        out = []
        for action in p._actions:
            out.extend(s for s in action.option_strings if s.startswith("--"))
        return out

    top = subactions(parser)
    lines = [
        "# ddlt shell completion — generated by `ddlt completion %s`" % shell,
        "_ddlt_complete() {",
        '    local cur="${COMP_WORDS[COMP_CWORD]}"',
        '    local verb="${COMP_WORDS[1]}"',
        '    local sub="${COMP_WORDS[2]}"',
        "    if [[ $COMP_CWORD -eq 1 ]]; then",
        '        COMPREPLY=( $(compgen -W "%s" -- "$cur") )' % " ".join(sorted(top)),
        "        return",
        "    fi",
        '    case "$verb" in',
    ]
    for name, p in sorted(top.items()):
        nested = subactions(p)
        words = sorted(set(list(nested) + flags(p)))
        lines.append(f"    {name})")
        if nested:
            lines.append("        if [[ $COMP_CWORD -eq 2 ]]; then")
            lines.append(
                '            COMPREPLY=( $(compgen -W "%s" -- "$cur") ); return'
                % " ".join(words)
            )
            lines.append("        fi")
            lines.append('        case "$sub" in')
            for sub_name, sub_p in sorted(nested.items()):
                lines.append(
                    f'        {sub_name}) COMPREPLY=( $(compgen -W '
                    f'"{" ".join(sorted(flags(sub_p)))}" -- "$cur") ); return;;'
                )
            lines.append("        esac")
            lines.append(
                '        COMPREPLY=( $(compgen -W "%s" -- "$cur") );;'
                % " ".join(sorted(flags(p)))
            )
        else:
            lines.append(
                '        COMPREPLY=( $(compgen -W "%s" -- "$cur") );;'
                % " ".join(words)
            )
    lines += [
        "    esac",
        "}",
        "complete -F _ddlt_complete ddlt",
    ]
    if shell == "zsh":
        lines = [
            "# zsh via bashcompinit",
            "autoload -U +X bashcompinit && bashcompinit",
        ] + lines
    try:
        print("\n".join(lines))
    except BrokenPipeError:  # `ddlt completion bash | head` is fine
        pass
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    parser = build_parser()
    args, extra = parser.parse_known_args(argv)
    if extra and args.command not in (
        "imagenet", "bert", "transformer", "benchmark", "experiment", "train"
    ):
        parser.error(f"unrecognized arguments: {' '.join(extra)}")

    if args.command is None:
        parser.print_help()
        return 0
    if args.command == "version":
        print(__version__)
        return 0

    if args.command == "config":
        cfg = load_config(args.env_file)
        if args.config_command == "show":
            for key in sorted(cfg.values):
                print(f"{key}={cfg.values[key]}")
        else:  # set
            cfg.persist(args.key.upper(), args.value)
            print(f"{args.key.upper()}={args.value} -> {cfg.env_path}")
        return 0

    if args.command == "login":
        cfg, runner, _ = _control(args)
        runner.run(["gcloud", "auth", "login"], capture=False, check=False)
        return 0

    if args.command == "select-project":
        cfg, runner, _ = _control(args)
        project = args.project or cfg.get("GCP_PROJECT")
        if not project and sys.stdin.isatty():
            # Interactive chooser — ``inv select-subscription`` parity
            # (``tasks.py:56-71``): tabulate the account's projects, prompt
            # by number, persist the choice.
            import json as _json

            listing = runner.run(
                ["gcloud", "projects", "list", "--format", "json"], check=False
            )
            try:
                projects = _json.loads(listing.stdout or "[]")
            except _json.JSONDecodeError:
                projects = []
            if projects:
                print(f"{'#':<4}{'PROJECT_ID':<32}{'NAME':<28}")
                print("-" * 64)
                for i, p in enumerate(projects):
                    print(
                        f"{i:<4}{p.get('projectId', ''):<32}"
                        f"{p.get('name', ''):<28}"
                    )
                choice = input("select project #: ").strip()
                try:
                    project = projects[int(choice)]["projectId"]
                except (ValueError, IndexError):
                    print(f"invalid selection {choice!r}", file=sys.stderr)
                    return 1
        if not project:
            result = runner.run(
                ["gcloud", "config", "get-value", "project"], check=False
            )
            project = (result.stdout or "").strip()
            if not project or project == "(unset)":
                print(
                    "no project given or configured; pass --project", file=sys.stderr
                )
                return 1
        runner.run(["gcloud", "config", "set", "project", project], check=False)
        cfg.persist("GCP_PROJECT", project)
        print(f"GCP_PROJECT={project} -> {cfg.env_path}")
        return 0

    if args.command == "setup":
        return _cmd_setup(args)

    if args.command == "delete":
        from distributeddeeplearning_tpu.control.storage import GcsStorage
        from distributeddeeplearning_tpu.control.tpu import pod_from_settings

        cfg, runner, _ = _control(args)
        pod_from_settings(cfg, runner).delete()
        if args.storage and cfg.get("GCS_BUCKET"):
            GcsStorage(runner, bucket=cfg.get("GCS_BUCKET")).delete_bucket()
        return 0

    if args.command == "lint":
        return _cmd_lint(args)
    if args.command == "tpu":
        return _cmd_tpu(args)
    if args.command == "train":
        return _cmd_train(args, extra)
    if args.command == "serve":
        return _cmd_serve(args)
    if args.command == "obs":
        return _cmd_obs(args)
    if args.command == "storage":
        return _cmd_storage(args)
    if args.command in (
        "imagenet", "bert", "transformer", "benchmark", "experiment"
    ):
        return _submit(args, args.command, extra)
    if args.command == "completion":
        return _emit_completion(parser, args.shell)
    if args.command == "interactive":
        from distributeddeeplearning_tpu.control.tpu import pod_from_settings

        cfg, runner, registry = _control(args)
        if args.repl:
            return _repl(cfg, runner, registry)
        pod_from_settings(cfg, runner).interactive(worker=args.worker)
        return 0
    if args.command == "tensorboard":
        return _cmd_tensorboard(args)
    if args.command == "runs":
        cfg, runner, registry = _control(args)
        experiment = args.experiment or cfg.get("EXPERIMENT_NAME") or "experiment"
        if args.run:
            if getattr(args, "refresh", False):
                from distributeddeeplearning_tpu.control.submit import Submitter

                try:
                    record = Submitter(cfg, runner, registry).poll_run(
                        experiment, args.run
                    )
                except ValueError:
                    record = None
            else:
                record = registry.find(experiment, args.run)
            path = (record.extra.get("metrics_path") if record else None) or str(
                registry.run_dir_for(experiment, args.run) / "metrics.jsonl"
            )
            content = _read_text_maybe_gs(path)
            if getattr(args, "metrics_only", False):
                if content is None:
                    print(f"no metrics recorded for {experiment}/{args.run}")
                    return 1
                print(content.rstrip())
                return 0
            if record is None:
                print(f"unknown run {experiment}/{args.run}")
                return 1
            print(
                f"{record.experiment}/{record.run_id}: {record.workload} "
                f"({record.mode}) status={record.status}"
                + (f" rc={record.returncode}" if record.returncode is not None else "")
            )
            if record.extra.get("poll"):
                print(f"  poll: {record.extra['poll']}")
            tail_n = getattr(args, "tail", 20)
            log_path = record.extra.get("log_path") or str(
                registry.run_dir_for(experiment, args.run) / "log.txt"
            )
            log = _read_text_maybe_gs(log_path) if tail_n else None
            if log:
                lines = log.rstrip().splitlines()[-tail_n:]
                print(f"--- log tail ({log_path}) ---")
                for line in lines:
                    print(line)
            if content:
                print("--- metrics ---")
                print(content.rstrip())
            return 0
        print(
            registry.format_runs(
                experiment, args.last, status=getattr(args, "status", None)
            )
        )
        return 0
    if args.command == "experiments":
        _, _, registry = _control(args)
        for name in registry.experiments():
            print(name)
        return 0
    if args.command == "new":
        from distributeddeeplearning_tpu.generator import generate_project

        cfg = load_config(args.env_file)
        path = generate_project(
            args.name,
            output_dir=args.output_dir,
            gcp_project=args.gcp_project,
            gcp_zone=args.gcp_zone or cfg.get("GCP_ZONE"),
            tpu_type=args.tpu_type or cfg.get("TPU_TYPE"),
            gcs_bucket=args.gcs_bucket,
        )
        print(f"generated project at {path}")
        return 0

    parser.print_help()
    return 2


def _read_text_maybe_gs(path: str):
    """File contents, following gs:// via tf.io.gfile; None when absent."""
    if path.startswith("gs://"):
        import tensorflow as tf

        if not tf.io.gfile.exists(path):
            return None
        with tf.io.gfile.GFile(path, "r") as f:
            return f.read()
    from pathlib import Path as _Path

    p = _Path(path)
    return p.read_text() if p.exists() else None


def _pin_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Pin this process (and the children it starts) to the CPU platform
    before the first backend query — for the hermetic self-check verbs
    (``ddlt lint``, ``ddlt obs attrib``), whose help says so.
    ``n_devices`` also asks for a virtual pod of that size unless
    ``XLA_FLAGS`` already sizes one."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    import jax

    # covers a jax imported before the environment was set
    jax.config.update("jax_platforms", "cpu")


def _cmd_lint(args) -> int:
    """``ddlt lint``: run both analyzer layers, print findings with
    file:line + fix hint, exit non-zero on any unwaived finding."""
    import dataclasses as _dc
    import json as _json
    import os

    if not args.no_programs:
        # the program audits trace on abstract shapes — ask for an
        # 8-device virtual CPU pod BEFORE the first backend query (the
        # collective-signature checks need real data shards, and lint
        # must never take a chip).  If a backend is already live the pin
        # is a no-op and any device-count-gated audit that cannot run is
        # reported below, not swallowed.
        _pin_cpu_platform(n_devices=8)
    from distributeddeeplearning_tpu.analysis import (
        format_findings,
        run_lint,
    )

    findings = run_lint(programs=not args.no_programs)
    if not args.no_programs:
        from distributeddeeplearning_tpu.analysis.program_audit import (
            skipped_audits,
        )

        for note in skipped_audits():
            print(f"ddlt lint: SKIPPED {note}", file=sys.stderr)
    if args.json:
        print(_json.dumps([_dc.asdict(f) for f in findings], indent=2))
    else:
        print(format_findings(findings, os.getcwd()))
    return 1 if findings else 0


def _cmd_setup(args) -> int:
    """Provision + data pipeline orchestration (``tasks.py setup:98-117``):
    bucket → prepare imagenet → upload images → tfrecords → upload."""
    from distributeddeeplearning_tpu.control.storage import (
        GcsStorage,
        generate_tfrecords_gated,
    )

    cfg, runner, _ = _control(args)
    bucket_name = cfg.get("GCS_BUCKET")
    storage = None
    if bucket_name:
        storage = GcsStorage(
            runner,
            bucket=bucket_name,
            project=cfg.get("GCP_PROJECT") or None,
            location=cfg.get("REGION") or None,
        )
        storage.ensure_bucket(cfg)
    else:
        logger.warning("GCS_BUCKET unset — skipping bucket provisioning")

    if args.skip_imagenet:
        print("setup complete (imagenet skipped)")
        return 0

    data_dir = cfg.get("DATA_DIR", "/data")
    tfrecords_dir = f"{data_dir.rstrip('/')}/tfrecords"
    if args.dry_run:
        # The data plane is plain Python (no CommandRunner seam): honour
        # --dry-run by describing the heavy work instead of doing it.
        if args.train_tar:
            print(f"[dry-run] prepare_imagenet({args.train_tar}) -> {data_dir}")
        if storage is not None:
            storage.upload_images(data_dir)
        if not args.skip_tfrecords:
            print(f"[dry-run] generate_tfrecords({data_dir}) -> {tfrecords_dir}")
            if storage is not None:
                storage.upload_tfrecords(tfrecords_dir)
        print("setup complete (dry run)")
        return 0
    if args.train_tar and args.val_tar:
        from distributeddeeplearning_tpu.data.prepare_imagenet import (
            prepare_imagenet,
        )

        prepare_imagenet(args.train_tar, args.val_tar, data_dir, args.val_map)
    if storage is not None:
        storage.upload_images(data_dir)
    if not args.skip_tfrecords:
        generate_tfrecords_gated(data_dir, tfrecords_dir, force=args.force)
        if storage is not None:
            storage.upload_tfrecords(tfrecords_dir)
    print("setup complete")
    return 0


def _cmd_train(args, extra: List[str]) -> int:
    """``ddlt train`` — the in-process restart supervisor.

    Runs the workload's ``main`` in THIS process and re-enters it on
    restartable failures (``train/resilience.py``): a preemption that
    landed its emergency checkpoint, an anomaly abort, or a data-stream
    death.  Because the workloads default to ``resume=True``, each restart
    continues from the latest checkpoint — pass ``--save_filepath`` or the
    restarts begin from scratch.  Exhausting the budget on a preemption
    exits ``RESUMABLE_EXIT_CODE`` (75) so an OUTER supervisor (k8s, the
    control plane's resubmit loop) can take over; other exhausted failures
    exit 1.
    """
    import importlib
    import os

    from distributeddeeplearning_tpu.control.submit import WORKLOAD_MODULES
    from distributeddeeplearning_tpu.train import resilience
    from distributeddeeplearning_tpu.utils import faults
    from distributeddeeplearning_tpu.utils.faults import DataStreamDeath
    from distributeddeeplearning_tpu.workloads._runner import (
        coerce_flags,
        parse_flags,
    )

    if args.max_restarts < 0:
        print("--max-restarts must be >= 0", file=sys.stderr)
        return 2
    if args.faults is not None:
        os.environ[faults.ENV_VAR] = args.faults
    # Fresh plan per invocation: one-shot faults re-arm for THIS run but
    # stay fired across its in-process restarts.
    faults.reset()

    workload = args.train_workload
    module = importlib.import_module(WORKLOAD_MODULES[workload])
    kwargs = coerce_flags(module.main, parse_flags(extra))
    # first-class comm flags (the passthrough contract still accepts the
    # --comm_overlap spelling for workloads that grow more knobs)
    import inspect

    wl_params = inspect.signature(module.main).parameters
    for key in ("comm_overlap", "bucket_mb", "comm_dtype",
                "weight_update_sharding"):
        value = getattr(args, key)
        if value is None:
            continue
        if key not in wl_params:
            print(
                f"--{key.replace('_', '-')} is not supported by the "
                f"{workload} workload", file=sys.stderr,
            )
            return 2
        kwargs[key] = value
    if args.dry_run:
        flags = " ".join(f"--{k} {v}" for k, v in kwargs.items())
        print(
            f"[dry-run] supervise {workload} (max_restarts="
            f"{args.max_restarts}) {flags}".rstrip()
        )
        return 0
    if args.max_restarts and not kwargs.get("save_filepath"):
        logger.warning(
            "--max-restarts without --save_filepath: restarts will begin "
            "from scratch (no checkpoint to resume from)"
        )
    from distributeddeeplearning_tpu.utils.hardware import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    def attempt(i: int):
        if i:
            print(f"[train] restart {i}/{args.max_restarts}", file=sys.stderr)
        return module.main(**kwargs)

    def latest_ckpt_step() -> int:
        # VERIFIED generations only (train/checkpoint.py manifests): the
        # supervisor's recovery accounting must count from the step a
        # restart can actually restore — a corrupt/torn latest generation
        # is not it (legacy manifest-less dirs still read as before)
        from distributeddeeplearning_tpu.train.checkpoint import (
            latest_verified_step_in_dir,
        )

        ckpt_dir = kwargs.get("save_filepath")
        if not ckpt_dir:
            return 0
        return latest_verified_step_in_dir(ckpt_dir) or 0

    redone = {"steps": 0}

    def on_restart(i: int, exc: BaseException) -> None:
        # recovery-cost accounting: how many completed steps the restart
        # re-does (0 when the emergency checkpoint landed at the exact
        # failure step; >0 when resuming from an older periodic save)
        at = getattr(exc, "step", None)
        if at is None:
            return
        done = at if isinstance(exc, resilience.PreemptionError) else at - 1
        redone["steps"] += max(done - latest_ckpt_step(), 0)

    restartable = (resilience.RestartableError, DataStreamDeath, StopIteration)
    try:
        result, restarts = resilience.supervise(
            attempt, max_restarts=args.max_restarts, restart_on=restartable,
            on_restart=on_restart,
            # restart markers interleave with the Trainer's per-attempt
            # segments in the goodput ledger (obs/goodput.py), so the
            # stitched file carries the SUPERVISOR's restart evidence too
            ledger_path=kwargs.get("goodput_path"),
        )
    except resilience.PreemptionError as exc:
        print(
            f"[train] {exc} — restart budget exhausted; exiting "
            f"{resilience.RESUMABLE_EXIT_CODE} (resumable)",
            file=sys.stderr,
        )
        return resilience.RESUMABLE_EXIT_CODE
    except restartable as exc:
        print(
            f"[train] {type(exc).__name__}: {exc} — restart budget "
            "exhausted; giving up",
            file=sys.stderr,
        )
        return 1
    if (
        isinstance(result, tuple) and len(result) == 2
        and hasattr(result[1], "anomalous_steps")
    ):
        state, fit = result
        print(
            f"[train] {workload} completed at step {int(state.step)}: "
            f"restarts={restarts} redone_steps={redone['steps']} "
            f"anomalous_steps={fit.anomalous_steps} "
            f"rollbacks={fit.rollbacks} "
            f"images_per_second={fit.images_per_second:.1f}"
        )
    else:
        print(f"[train] {workload} completed: restarts={restarts}")
    if kwargs.get("goodput_path"):
        # run-level goodput summary over the stitched per-attempt
        # segments (the same accounting bench.py --goodput artifacts)
        from distributeddeeplearning_tpu.obs import goodput

        try:
            summary = goodput.summarize_ledger(
                goodput.stitch(kwargs["goodput_path"])
            )
            print(
                f"[train] goodput_fraction={summary['goodput_fraction']} "
                f"recovery_s={summary['seconds']['recovery']} "
                f"steps_redone={summary['counts'].get('steps_redone', 0)} "
                f"unaccounted_pct={summary['unaccounted_pct']}"
            )
        except Exception as exc:  # accounting must never fail the run
            print(f"[train] goodput summary unavailable: {exc}",
                  file=sys.stderr)
    return 0


def _read_prompts(args):
    """[(uid, token-id list)] from --prompt-file / stdin (one prompt per
    line, whitespace-separated integer token ids — the LM is id-based; no
    tokenizer ships with the framework)."""
    if args.prompt_file and args.prompt_file != "-":
        with open(args.prompt_file) as f:
            lines = f.readlines()
    elif args.prompt_file is None and sys.stdin.isatty():
        return []  # interactive terminal, nothing piped
    else:
        lines = sys.stdin.readlines()
    prompts = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            ids = [int(tok) for tok in line.split()]
        except ValueError:
            raise SystemExit(
                f"prompt line {i + 1} is not whitespace-separated token ids: "
                f"{line[:60]!r}"
            )
        if ids:
            prompts.append((f"line{i + 1}", ids))
    return prompts


def _cmd_serve(args) -> int:
    """``ddlt serve`` — the serving column's CLI entry point.

    Builds the KV-cached engine (``serve.engine``) over a
    ``pipelined_transformer`` LM — randomly initialized at the ``--num-
    layers/--d-model/...`` dims, or restored from ``--checkpoint-dir`` —
    and drives the continuous-batching scheduler over the prompt source.
    Completions go to stdout as ``uid<TAB>token ids``; the stats JSON goes
    to stdout for ``--synthetic`` (the SERVE artifact line) or stderr
    otherwise, and to ``--report`` when given.
    """
    import json as _json

    # --speculative flag-combination guards, at parse time: the
    # acceptance rule is greedy-only (argmax comparison) and extends the
    # decode==full-forward bit-exactness pin, which needs the f32 cache.
    # Erroring HERE beats silently serving non-equivalent samples after
    # a full engine build.
    if args.speculative:
        if args.temperature > 0:
            print(
                "--speculative is greedy-only for now: the acceptance "
                "rule compares argmaxes, so temperature "
                f"{args.temperature} would silently produce samples NOT "
                "equivalent to non-speculative decoding.  Drop "
                "--temperature (or set it to 0).",
                file=sys.stderr,
            )
            return 1
        if args.quantize_kv is not None:
            print(
                "--speculative requires the f32 KV cache: the verifier "
                "extends the decode==full-forward bit-exactness pin, "
                "which the int8 grid breaks.  Use --draft-weights int8 "
                "for the int8 DRAFTER (the f32 model still verifies).",
                file=sys.stderr,
            )
            return 1
        if args.replicas > 1:
            print(
                "--speculative is single-replica for now (the fleet "
                "spec does not carry drafter state)", file=sys.stderr,
            )
            return 1
        if args.draft_tokens < 1:
            print("--draft-tokens must be >= 1", file=sys.stderr)
            return 1
        if args.draft_layers is not None and args.draft_layers < 1:
            print("--draft-layers must be >= 1", file=sys.stderr)
            return 1

    if args.synthetic:
        prompts = None
    else:
        prompts = _read_prompts(args)
        if not prompts:
            print("no prompts (use --synthetic, --prompt-file or stdin)",
                  file=sys.stderr)
            return 1

    if args.dry_run:
        n = args.requests if args.synthetic else len(prompts)
        print(
            f"[dry-run] serve {n} request(s), {args.batch_slots} slots, "
            f"max_new_tokens={args.max_new_tokens}"
        )
        return 0

    import jax
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.serve import (
        ContinuousBatchingScheduler,
        Request,
        data_parallel_engine,
        synthetic_requests,
    )
    from distributeddeeplearning_tpu.utils.hardware import (
        device_summary,
        enable_compilation_cache,
    )

    enable_compilation_cache()

    if args.top_k is not None and args.top_k < 1:
        print("--top-k must be >= 1", file=sys.stderr)
        return 1
    if args.synthetic and args.requests < 1:
        print("--requests must be >= 1", file=sys.stderr)
        return 1

    # Multi-tenant knob guards, at parse time (the PR 8 rule: a bad knob
    # fails HERE with one line, not as a traceback after a full engine
    # build — or, worse on the fleet path, as N identical spawn errors).
    priority_classes = ("premium", "standard", "best_effort")
    if args.priority_classes is not None:
        priority_classes = tuple(
            c.strip() for c in args.priority_classes.split(",")
        )
        if not priority_classes or any(not c for c in priority_classes):
            print(
                "--priority-classes must be a non-empty comma-separated "
                f"list (got {args.priority_classes!r})", file=sys.stderr,
            )
            return 1
        if len(set(priority_classes)) != len(priority_classes):
            print(
                f"--priority-classes has duplicates: "
                f"{args.priority_classes!r}", file=sys.stderr,
            )
            return 1
    if args.shed_policy not in ("block", "shed"):
        print(
            f"--shed-policy must be 'block' or 'shed' "
            f"(got {args.shed_policy!r})", file=sys.stderr,
        )
        return 1
    if args.preempt_budget < 0:
        print("--preempt-budget must be >= 0", file=sys.stderr)
        return 1
    class_slos = None
    if args.tenant_slo:
        if args.replicas <= 1:
            print(
                "--tenant-slo needs --replicas > 1: per-class SLOs are "
                "evaluated over the bucket-merged FLEET metrics (single-"
                "replica runs report per-class latency in the stats "
                "JSON instead)", file=sys.stderr,
            )
            return 1
        from distributeddeeplearning_tpu.obs.fleet import parse_class_slos

        try:
            class_slos = parse_class_slos(args.tenant_slo)
        except ValueError as exc:
            print(f"--tenant-slo: {exc}", file=sys.stderr)
            return 1
        unknown = sorted(set(class_slos) - set(priority_classes))
        if unknown:
            print(
                f"--tenant-slo names unknown class(es) {unknown} — "
                f"declared priority classes: {list(priority_classes)}",
                file=sys.stderr,
            )
            return 1

    # Checkpoint FIRST: synthetic prompts and validation must see the
    # restored model's real vocab/position table, not the dim flags.
    params = None
    ckpt_vocab = ckpt_max_len = None
    served_model = None
    if args.model_config:
        # another architecture through the same engine and scheduler: the
        # engine takes it as one description and refuses, by name, what it
        # cannot serve for it yet; what never reaches the engine stops here
        for flag, bad in (
            ("--kv-layout dense", args.kv_layout != "paged"),
            ("--checkpoint-dir", bool(args.checkpoint_dir)),
            ("--quantize-weights", args.quantize_weights is not None),
            ("--speculative", args.speculative),
            ("--replicas > 1", args.replicas > 1),
        ):
            if bad:
                print(f"--model-config cannot run with {flag} yet",
                      file=sys.stderr)
                return 1
        import jax.numpy as jnp

        from distributeddeeplearning_tpu.models import (
            hybrid_moe_transformer as hybrid,
        )
        from distributeddeeplearning_tpu.serve.served_model import (
            hybrid_model,
        )

        with open(args.model_config) as f:
            model_cfg = _json.load(f)
        spec = hybrid.spec_from_config(model_cfg)
        served_model = hybrid_model(spec)
        params = hybrid.init_params(
            jax.random.key(args.seed), spec,
            dtype=jnp.dtype(model_cfg.get("storage_dtype", "bfloat16")),
        )
        ckpt_vocab = spec.vocab_size
    if args.checkpoint_dir:
        if args.num_heads is None:
            # a wrong-but-dividing default would reshape K/V into the
            # wrong head grouping and generate garbage with no error
            print(
                "--checkpoint-dir requires an explicit --num-heads "
                "matching the training config (not derivable from the "
                "saved qkv shapes)", file=sys.stderr,
            )
            return 1
        if args.replicas > 1:
            # the router holds no chip (its workers each own one), so it
            # reads the two shapes it validates against from the verified
            # generation's manifest instead of restoring the weights
            from distributeddeeplearning_tpu.train.checkpoint import (
                verified_param_shapes,
            )

            shapes = verified_param_shapes(args.checkpoint_dir)
            if shapes is None:
                print(
                    f"no manifested checkpoint under {args.checkpoint_dir}"
                    " (fleet serving reads shapes from the manifest)",
                    file=sys.stderr,
                )
                return 1
            ckpt_vocab = shapes["['head']"][1]
            ckpt_max_len = shapes["['pos']"][0]
        else:
            from distributeddeeplearning_tpu.train.checkpoint import (
                Checkpointer,
            )

            ckpt = Checkpointer(args.checkpoint_dir)
            try:
                params, step = ckpt.restore_params()
            finally:
                ckpt.close()
            if params is None:
                print(f"no checkpoint under {args.checkpoint_dir}",
                      file=sys.stderr)
                return 1
            # restore_params walks generations newest-first and verifies
            # each candidate against its manifest (train/checkpoint.py) —
            # a corrupt latest falls back instead of serving garbage
            print(
                f"[serve] restored verified params at step {step}",
                file=sys.stderr,
            )
            ckpt_vocab = params["head"].shape[1]
            ckpt_max_len = params["pos"].shape[0]
    num_heads = args.num_heads if args.num_heads is not None else 4
    vocab = ckpt_vocab if ckpt_vocab is not None else args.vocab_size

    if args.synthetic:
        prompts = [
            (r.uid, r.prompt)
            for r in synthetic_requests(
                args.requests, vocab_size=vocab,
                max_prompt=args.prompt_len,
                shared_prefix_len=args.shared_prefix_len,
                rng=np.random.default_rng(args.seed),
            )
        ]
    max_prompt = max(len(p) for _, p in prompts)
    max_seq = args.max_seq or (max_prompt + args.max_new_tokens)
    if not args.max_seq and args.kv_layout == "dense" and max_seq > 128:
        # a derived window rounds up to a length the dense layout's
        # kernels tile (the engine refuses the others)
        max_seq = -(-max_seq // 128) * 128
    if ckpt_max_len is not None and ckpt_max_len < max_seq:
        # say so: 'raise --max-seq' can never beat this cap
        print(
            f"[serve] max_seq {max_seq} clamped to the checkpoint's "
            f"position table {ckpt_max_len}", file=sys.stderr,
        )
        max_seq = ckpt_max_len
    if params is None and args.replicas <= 1:
        # fleet workers build their own params from the spec — the
        # router process materializing a model it never serves would
        # cost a full extra init + resident copy for the fleet's life.
        # (Prompt validation below needs only vocab/max_seq, both known
        # here; a restored checkpoint is still loaded above for its
        # true head vocab and position-table clamp.)
        params = init_params(
            jax.random.key(args.seed),
            num_layers=args.num_layers, d_model=args.d_model,
            num_heads=num_heads, d_ff=args.d_ff,
            vocab_size=vocab, max_len=max_seq,
        )

    # Validate up front: engine.prefill raising mid-run (a too-small
    # --max-seq or the position-table clamp) would discard every
    # already-finished completion.
    too_long = [(uid, len(p)) for uid, p in prompts if len(p) >= max_seq]
    if too_long:
        uid, n = too_long[0]
        print(
            f"{len(too_long)} prompt(s) leave no room to generate at "
            f"max_seq={max_seq} (first: {uid}, {n} tokens) — raise "
            "--max-seq (up to the model's position table) or shorten "
            "the prompts",
            file=sys.stderr,
        )
        return 1
    # ... and ids against the ACTUAL model vocab (the restored head, not
    # the flag): jit's gather clamps out-of-range ids silently, which
    # would decode a plausible completion from a wrong prompt.
    bad = [
        (uid, t) for uid, p in prompts for t in p if not 0 <= t < vocab
    ]
    if bad:
        uid, t = bad[0]
        print(
            f"{len(bad)} prompt token id(s) outside the model vocab "
            f"[0, {vocab}) (first: {uid}, id {t})",
            file=sys.stderr,
        )
        return 1

    if args.replicas > 1:
        # Fleet path: N replica worker processes behind the supervising
        # router (serve/fleet.py).  Workers build their own engines from
        # the spec — params never cross the process boundary — so the
        # engine build below is skipped entirely.  SIGTERM drains the
        # fleet and the process exits 75 (RESUMABLE_EXIT_CODE): the
        # control plane's resubmit path treats a drained server exactly
        # like a preempted training run.
        from distributeddeeplearning_tpu.serve.fleet import (
            ReplicaPlacementError,
            ReplicaSpec,
            serve_fleet,
        )
        from distributeddeeplearning_tpu.train.resilience import (
            RESUMABLE_EXIT_CODE,
        )
        from distributeddeeplearning_tpu.utils.virtual_pod import (
            is_virtual_pod,
        )

        if args.trace_dir:
            print("[serve] --trace-dir is per-process; fleet runs emit "
                  "obs events but no merged device trace", file=sys.stderr)
        if args.quantize_weights and args.calib_prompts:
            print("[serve] fleet workers quantize weights without "
                  "calibration (--calib-prompts is single-replica only)",
                  file=sys.stderr)
        spec = ReplicaSpec(
            model=(
                {} if args.checkpoint_dir else dict(
                    num_layers=args.num_layers, d_model=args.d_model,
                    num_heads=num_heads, d_ff=args.d_ff,
                    vocab_size=vocab, max_len=max_seq,
                )
            ),
            seed=args.seed,
            checkpoint_dir=args.checkpoint_dir,
            quantize_weights=args.quantize_weights,
            num_heads=num_heads,
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            kv_layout=args.kv_layout,
            page_size=args.page_size,
            num_pages=args.kv_pages,
            prefill_chunk=args.prefill_chunk,
            prefix_cache=not args.no_prefix_cache,
            prefill_attention=args.prefill_attention,
            cache_dtype=args.quantize_kv,
            temperature=args.temperature,
            top_k=args.top_k,
            eos_id=args.eos_id,
            max_new_tokens=args.max_new_tokens,
            request_deadline_s=args.request_deadline_s,
            watchdog_deadline_s=args.watchdog_deadline_s,
            decode_kernel=args.decode_kernel,
            priority_classes=priority_classes,
            shed_policy=args.shed_policy,
            preempt_budget=args.preempt_budget,
        )
        fleet_requests = [Request(uid=uid, prompt=p) for uid, p in prompts]
        if class_slos and args.synthetic:
            # synthetic smoke traffic is single-class ("standard") — an
            # SLO'd class with zero samples FAILS by design, so deal the
            # synthetic requests round-robin across the SLO'd classes
            # (same convention as `ddlt obs fleet --slo-per-tenant`);
            # real prompt traffic keeps whatever classes it arrived with
            import dataclasses as _dc
            slo_classes = sorted(class_slos)
            fleet_requests = [
                _dc.replace(
                    r, tenant=slo_classes[i % len(slo_classes)],
                    priority=slo_classes[i % len(slo_classes)],
                )
                for i, r in enumerate(fleet_requests)
            ]
        try:
            results, freport = serve_fleet(
                spec,
                fleet_requests,
                replicas=args.replicas,
                max_restarts=args.max_restarts,
                max_redeliveries=args.max_redeliveries,
                heartbeat_timeout_s=args.heartbeat_timeout_s,
                install_signals=True,
            )
        except ReplicaPlacementError as exc:
            print(f"--replicas: {exc}", file=sys.stderr)
            return 2
        stats = freport.to_dict()
        # the router never touched a backend: the platform is the one the
        # workers reported in their ready handshake
        stats["platform"] = freport.device.get("platform")
        stats["virtual_pod"] = is_virtual_pod()
        slo_violated = False
        if class_slos:
            from distributeddeeplearning_tpu.obs.fleet import (
                evaluate_class_slos,
            )

            verdict = evaluate_class_slos(
                class_slos,
                fleet_report=stats,
                per_class_latency=stats.get(
                    "fleet_latency_per_class", {}
                ),
            )
            stats["slo_per_tenant"] = verdict
            for cls, res in sorted(verdict["per_class"].items()):
                status = "PASS" if res["pass"] else "FAIL"
                print(f"[serve] tenant SLO {cls}: {status}",
                      file=sys.stderr)
            slo_violated = not verdict["pass"]
        if args.synthetic:
            print(_json.dumps(stats))
        else:
            for r in results:
                print(f"{r.uid}\t{' '.join(str(t) for t in r.tokens)}")
            print(_json.dumps(stats), file=sys.stderr)
        if args.report:
            with open(args.report, "w") as f:
                _json.dump(stats, f, indent=2)
                f.write("\n")
            print(f"[serve] report -> {args.report}", file=sys.stderr)
        if freport.drained:
            return RESUMABLE_EXIT_CODE
        return 1 if slo_violated else 0

    # Weight PTQ after validation (the checks above need the f32 head's
    # true vocab) and before engine build: with --calib-prompts the
    # quantized pytree ships with its fidelity numbers, the go/no-go a
    # deployment reads before flipping traffic to the int8 path.
    if args.quantize_weights == "int8":
        from distributeddeeplearning_tpu.quant.calibrate import (
            calibrate_params,
            quantize_params,
        )

        if args.calib_prompts > 0:
            calib = [
                r.prompt
                for r in synthetic_requests(
                    args.calib_prompts, vocab_size=vocab,
                    max_prompt=min(args.prompt_len, max_seq - 1),
                    rng=np.random.default_rng(args.seed + 1),
                )
            ]
            params, creport = calibrate_params(
                params, calib, num_heads=num_heads
            )
            print(
                f"[serve] int8 weights: calibration over "
                f"{creport.num_prompts} prompts — logit MAE "
                f"{creport.logit_mae:.6f} (max {creport.logit_mae_max:.6f}),"
                f" greedy agreement {creport.greedy_agreement:.1%}",
                file=sys.stderr,
            )
        else:
            params = quantize_params(params)
            print("[serve] int8 weights: quantized without calibration "
                  "(--calib-prompts 0)", file=sys.stderr)
    cache_dtype = None
    if args.quantize_kv == "int8":
        import jax.numpy as jnp

        cache_dtype = jnp.int8

    if args.kv_layout == "paged":
        from distributeddeeplearning_tpu.serve import PagedInferenceEngine

        if args.page_size < 1 or args.prefill_chunk < 1:
            print("--page-size and --prefill-chunk must be >= 1",
                  file=sys.stderr)
            return 1
        # single-mesh: the block-table gather crosses the page axis, so
        # the paged pool does not shard over devices (the dense layout
        # remains the multi-chip path)
        from distributeddeeplearning_tpu.serve.served_model import Refused

        try:
            engine, mesh = PagedInferenceEngine(
                params,
                num_heads=None if served_model is not None else num_heads,
                model=served_model,
                batch_slots=args.batch_slots,
                max_seq=max_seq,
                page_size=args.page_size,
                num_pages=args.kv_pages,
                prefill_chunk=args.prefill_chunk,
                temperature=args.temperature,
                top_k=args.top_k,
                cache_dtype=cache_dtype,
                rng=jax.random.key(args.seed),
                prefix_cache=not args.no_prefix_cache,
                decode_kernel=args.decode_kernel,
            ), None
        except Refused as exc:
            print(f"[serve] {exc}", file=sys.stderr)
            return 1
    elif args.speculative:
        # spec is single-mesh (the verify/rollback programs carry no
        # sharding annotations) — build the dense engine unmeshed
        from distributeddeeplearning_tpu.serve import InferenceEngine

        engine, mesh = InferenceEngine(
            params,
            num_heads=num_heads,
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            prefill_attention=args.prefill_attention,
            temperature=args.temperature,
            top_k=args.top_k,
            cache_dtype=cache_dtype,
            rng=jax.random.key(args.seed),
            decode_kernel=args.decode_kernel,
        ), None
    else:
        engine, mesh = data_parallel_engine(
            params,
            num_heads=num_heads,
            batch_slots=args.batch_slots,
            max_seq=max_seq,
            prefill_attention=args.prefill_attention,
            temperature=args.temperature,
            top_k=args.top_k,
            cache_dtype=cache_dtype,
            rng=jax.random.key(args.seed),
            decode_kernel=args.decode_kernel,
        )

    spec_decoder = None
    if args.speculative:
        from distributeddeeplearning_tpu.spec import (
            Int8Drafter,
            SpeculativeDecoder,
        )

        if args.draft_weights == "int8":
            qdraft = None
            if args.checkpoint_dir:
                # the int8 drafter pytree straight from the f32
                # checkpoint — no second full-precision copy held
                from distributeddeeplearning_tpu.train.checkpoint import (
                    Checkpointer,
                )

                ckpt = Checkpointer(args.checkpoint_dir)
                try:
                    qdraft, _ = ckpt.restore_params(
                        quantize_weights="int8"
                    )
                finally:
                    ckpt.close()
            spec_decoder = SpeculativeDecoder(
                engine, drafter=Int8Drafter(qdraft),
                draft_tokens=args.draft_tokens,
            )
        else:
            spec_decoder = SpeculativeDecoder(
                engine, drafter="truncated",
                draft_tokens=args.draft_tokens,
                draft_layers=args.draft_layers,
            )
        print(
            f"[serve] speculative: drafter={spec_decoder.drafter_name} "
            f"draft_tokens={args.draft_tokens}"
            + (
                f" draft_layers={spec_decoder.draft_layers}"
                if spec_decoder.drafter_name == "truncated" else ""
            ),
            file=sys.stderr,
        )
    scheduler = ContinuousBatchingScheduler(
        engine, eos_id=args.eos_id, max_new_tokens=args.max_new_tokens,
        request_deadline_s=args.request_deadline_s,
        watchdog_deadline_s=args.watchdog_deadline_s,
        spec_decoder=spec_decoder,
        priority_classes=priority_classes,
        shed_policy=args.shed_policy,
        preempt_budget=args.preempt_budget,
    )
    reqs = [Request(uid=uid, prompt=p) for uid, p in prompts]
    # SIGTERM -> graceful drain (stop admitting, finish active requests,
    # queued ones return "preempted") -> exit 75, the same resumable-exit
    # contract the training loop uses, so the control plane resubmits a
    # drained server like a preempted run
    import signal as _signal

    from distributeddeeplearning_tpu.train.resilience import (
        RESUMABLE_EXIT_CODE,
        PreemptionGuard,
    )

    guard = PreemptionGuard(signals=(_signal.SIGTERM,)).install()
    try:
        if args.trace_dir:
            # obs mode: host spans (request lifecycle, prefill chunks,
            # decode dispatch) + the jax.profiler device trace, merged
            # onto one Chrome-trace timeline under --trace-dir
            from distributeddeeplearning_tpu.obs import configure
            from distributeddeeplearning_tpu.obs.profile import (
                profile_and_merge,
            )

            tracer = configure(enabled=False)  # enabled inside the window

            def _serve_run():
                with tracer.span("serve/run", requests=len(reqs)):
                    return scheduler.run(reqs, should_drain=guard.preempted)

            (results, report), _, _, merged_path = profile_and_merge(
                _serve_run, trace_dir=args.trace_dir, tracer=tracer
            )
            print(f"[serve] merged trace -> {merged_path}", file=sys.stderr)
        else:
            results, report = scheduler.run(
                reqs, should_drain=guard.preempted
            )
    finally:
        guard.uninstall()

    import hashlib

    from distributeddeeplearning_tpu.utils.virtual_pod import is_virtual_pod

    stats = report.to_dict()
    device = device_summary()
    stats["platform"] = device["platform"]
    stats["device_kind"] = device["kind"]
    stats["device_count"] = device["count"]
    stats["virtual_pod"] = is_virtual_pod()
    stats["mesh_devices"] = device["count"] if mesh is not None else 1
    if device["platform"] == "tpu":
        # Pallas kernels exist on this platform: count the Mosaic calls in
        # the programs that just ran, so the report shows the kernel from
        # the program itself and not from the flag that asked for it
        from distributeddeeplearning_tpu.obs.attrib import mosaic_call_counts

        stats["mosaic_calls"] = mosaic_call_counts(engine.kernel_programs())
    # one digest over every (uid, tokens) pair: two greedy runs of the
    # same seed must agree on it, without shipping the streams around
    stats["token_digest"] = hashlib.sha256(
        _json.dumps(
            sorted((r.uid, list(r.tokens)) for r in results)
        ).encode()
    ).hexdigest()
    if args.trace_dir:
        stats["trace_dir"] = args.trace_dir
    if args.synthetic:
        print(_json.dumps(stats))
    else:
        for r in results:
            print(f"{r.uid}\t{' '.join(str(t) for t in r.tokens)}")
        print(_json.dumps(stats), file=sys.stderr)
    if args.report:
        with open(args.report, "w") as f:
            _json.dump(stats, f, indent=2)
            f.write("\n")
        print(f"[serve] report -> {args.report}", file=sys.stderr)
    if report.drained:
        return RESUMABLE_EXIT_CODE
    if args.synthetic and report.errors:
        # synthetic prompts are valid by construction, so an "error"
        # finish is the SYSTEM failing (a kernel that does not build, an
        # OOM) — per-request isolation kept the run alive to report it,
        # and the exit code must not call that a success
        first = next(r for r in results if r.finish_reason == "error")
        print(
            f"[serve] {report.errors} synthetic request(s) finished "
            f"'error' (first: {first.uid}: {first.error})",
            file=sys.stderr,
        )
        return 1
    return 0


def _cmd_obs(args) -> int:
    """``ddlt obs {serve,train}`` — the profiling harness as a verb.

    Wraps a short, self-contained run (synthetic traffic, tiny dims) in
    the obs tracer + ``jax.profiler.trace``, merges the two timelines
    onto one clock, snapshots the metrics registry, and prints a summary
    JSON line.  The trace dir then holds:

    - ``merged.trace.json`` — host spans + device profile, one file,
      opens directly in chrome://tracing / Perfetto;
    - ``obs-metrics.jsonl`` — the registry snapshot row(s);
    - the raw xprof trace (``plugins/profile/...``) for xprof tooling.

    For the real attribution artifact (f32-vs-int8 decode breakdown) use
    ``bench.py --obs``; this verb is the quick "show me the timeline of
    what this thing does" loop.
    """
    import json as _json
    import os

    if args.obs_command == "history":
        # pure artifact analysis — no jax, no backend init: the preflight
        # use (make perf-history) must stay seconds-cheap
        from distributeddeeplearning_tpu.obs.history import run_history

        rc, output = run_history(
            args.root, gate=args.gate, as_json=args.json
        )
        print(output)
        return rc
    if args.obs_command == "attrib":
        return _cmd_obs_attrib(args)
    if args.obs_command == "fleet":
        return _cmd_obs_fleet(args)

    import jax
    import numpy as np

    from distributeddeeplearning_tpu.obs import configure, get_registry
    from distributeddeeplearning_tpu.obs.profile import (
        profile_and_merge,
        summarize_timeline,
    )

    os.makedirs(args.trace_dir, exist_ok=True)
    tracer = configure(enabled=False)  # enabled inside the window

    if args.obs_command == "serve":
        import jax.numpy as jnp

        from distributeddeeplearning_tpu.models.pipelined_transformer import (
            init_params,
        )
        from distributeddeeplearning_tpu.serve import (
            ContinuousBatchingScheduler,
            PagedInferenceEngine,
            synthetic_requests,
        )

        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                    vocab_size=257)
        max_seq = args.prompt_len + args.max_new_tokens
        params = init_params(jax.random.key(0), max_len=max_seq, **dims)
        engine = PagedInferenceEngine(
            params, num_heads=dims["num_heads"],
            batch_slots=args.batch_slots, max_seq=max_seq,
            cache_dtype=jnp.int8 if args.quantize_kv == "int8" else None,
            rng=jax.random.key(1),
        )
        requests = synthetic_requests(
            args.requests, vocab_size=dims["vocab_size"],
            max_prompt=args.prompt_len,
            rng=np.random.default_rng(0),
        )

        def run():
            return ContinuousBatchingScheduler(
                engine, max_new_tokens=args.max_new_tokens
            ).run(requests)[1]

    else:  # train
        import itertools

        import jax.numpy as jnp

        from distributeddeeplearning_tpu.data.synthetic import (
            SyntheticDataset,
        )
        from distributeddeeplearning_tpu.models import get_model
        from distributeddeeplearning_tpu.parallel import (
            MeshSpec,
            create_mesh,
        )
        from distributeddeeplearning_tpu.train.loop import (
            Trainer,
            TrainerConfig,
        )
        from distributeddeeplearning_tpu.train.schedule import (
            goyal_lr_schedule,
        )
        from distributeddeeplearning_tpu.train.state import (
            create_train_state,
            sgd_momentum,
        )
        from distributeddeeplearning_tpu.train.step import build_train_step

        img = (32, 32, 3)
        mesh = create_mesh(MeshSpec())
        model = get_model("resnet18", num_classes=10, dtype=jnp.float32)
        tx = sgd_momentum(goyal_lr_schedule(0.05, 1, steps_per_epoch=100))
        state = create_train_state(
            jax.random.key(0), model, (args.batch_size, *img), tx
        )
        step = build_train_step(mesh, state, compute_dtype=jnp.float32)
        ds = SyntheticDataset(
            length=args.batch_size * (args.steps + 2), image_shape=img,
            num_classes=10,
        )
        trainer = Trainer(
            mesh, step,
            config=TrainerConfig(
                epochs=1, steps_per_epoch=args.steps,
                global_batch_size=args.batch_size, log_every=10**9,
                prefetch=0,
                obs_metrics_path=os.path.join(
                    args.trace_dir, "obs-metrics.jsonl"
                ),
            ),
        )

        def run():
            _, result = trainer.fit(
                state, itertools.cycle(ds.batches(args.batch_size))
            )
            return result

    def _windowed():
        with tracer.span(f"obs/{args.obs_command}"):
            return run()

    _, _, merged, merged_path = profile_and_merge(
        _windowed, trace_dir=args.trace_dir, tracer=tracer
    )
    snapshot_path = os.path.join(args.trace_dir, "obs-metrics.jsonl")
    if args.obs_command != "train":
        # train mode: the Trainer already appended one row per epoch via
        # obs_metrics_path (same file) — a second write here would leave
        # duplicate rows and double-count every epoch downstream
        get_registry().write_snapshot(snapshot_path, mode=args.obs_command)
    digest = summarize_timeline(merged, limit=20)
    print(_json.dumps({
        "mode": args.obs_command,
        "merged_trace": merged_path,
        "obs_metrics": snapshot_path,
        "event_counts": digest["event_counts"],
        "host_span_total_ms": digest["host_span_total_ms"],
    }))
    print(
        f"[obs] open {merged_path} in chrome://tracing or "
        "https://ui.perfetto.dev", file=sys.stderr,
    )
    return 0


def _cmd_obs_attrib(args) -> int:
    """``ddlt obs attrib [--check]`` — the attribution layer as a verb.

    Hermetic by construction: the verb builds its own tiny engines and
    traffic (no checkpoint, no network), so ``--check`` can run in CI
    and ``make obs-gate`` on any box.  Unless ``JAX_PLATFORMS`` says
    otherwise the CPU platform is pinned before the first backend query,
    same recipe as ``ddlt lint`` — a self-check must not take a chip."""
    import json as _json
    import os

    if "JAX_PLATFORMS" not in os.environ:
        _pin_cpu_platform()
    from distributeddeeplearning_tpu.obs.attrib import self_check

    ok, report = self_check(spec=not args.no_spec)
    if args.report:
        with open(args.report, "w") as f:
            _json.dump(report, f, indent=2)
            f.write("\n")
    if args.json:
        print(_json.dumps(report, indent=2))
    elif args.check:
        print(_json.dumps({
            "gates": report["gates"],
            "owner_match_pct": report["owner_match_pct"],
            "unaccounted_hbm_pct": report["unaccounted_hbm_pct"],
            "programs_covered": report["programs_covered"],
        }))
    else:
        for name, row in sorted(report["programs"].items()):
            flops = row["flops"] or 0.0
            nbytes = row["bytes_accessed"] or 0.0
            temp = row["temp_bytes"]
            line = (
                f"{name:<38} flops={flops:>12.0f} "
                f"bytes={nbytes:>12.0f}"
            )
            if temp is not None:
                line += f" temp={temp:>10d}"
            rf = row.get("roofline")
            if rf and rf.get("roofline_available"):
                line += (
                    f"  {rf['achieved_tflops']:.4f} TF/s "
                    f"({rf['pct_of_compute_roofline']:.2%} of "
                    f"{report['peaks_source']} compute peak, "
                    f"bound={rf['bound']})"
                )
            print(line)
        led = report["ledger"]
        for owner, row in sorted(led["owners"].items()):
            print(
                f"hbm.{owner:<20} {row['bytes']:>12d} B "
                f"(committed {row['committed_bytes']}, "
                f"peak {row['peak_bytes']})"
            )
        print(
            f"hbm total {led['total_bytes']} B of {led['live_bytes']} B "
            f"live ({report['unaccounted_hbm_pct']}% unaccounted, "
            f"limit {led['residual_limit_pct']}%)"
        )
        print(f"gates: {report['gates']}")
    if not all(report["gates"].values()):
        print("[obs attrib] GATE FAILED: " + ", ".join(
            k for k, v in report["gates"].items() if not v
        ), file=sys.stderr)
        return 1
    return 0


def _cmd_obs_fleet(args) -> int:
    """``ddlt obs fleet`` — fleet-scale observability as a verb.

    Runs a small multi-replica chaos fleet (synthetic traffic, tiny
    dims) with distributed tracing on: the router mints a trace id per
    request, every worker exports a Chrome-trace shard, and the merged
    ``fleet.trace.json`` shows the injected failover end-to-end under
    one trace id.  Fleet TTFT/TPOT come from bucket-merged worker
    histograms; the ``--slo`` spec is evaluated over them (exit 1 on
    violation) and any flight-recorder dumps ride the summary.

    For the gated artifact (``OBS_FLEET_r{NN}.json``) use ``bench.py
    --obs-fleet``; this verb is the quick "show me the fleet timeline"
    loop.
    """
    import dataclasses as _dc
    import json as _json

    import numpy as np

    from distributeddeeplearning_tpu.obs.fleet import (
        SLOSpec,
        observe_fleet,
        parse_class_slos,
    )
    from distributeddeeplearning_tpu.serve import (
        ReplicaSpec,
        synthetic_requests,
    )

    try:
        slo = SLOSpec.parse(args.slo)
    except ValueError as exc:
        print(f"bad --slo: {exc}", file=sys.stderr)
        return 1
    priority_classes = ("premium", "standard", "best_effort")
    class_slos = None
    if args.slo_per_tenant:
        try:
            class_slos = parse_class_slos(args.slo_per_tenant)
        except ValueError as exc:
            print(f"bad --slo-per-tenant: {exc}", file=sys.stderr)
            return 1
        unknown = sorted(set(class_slos) - set(priority_classes))
        if unknown:
            print(
                f"--slo-per-tenant names unknown class(es) {unknown} — "
                f"this smoke serves the classes {list(priority_classes)}",
                file=sys.stderr,
            )
            return 1
    dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                vocab_size=257)
    max_seq = args.prompt_len + args.max_new_tokens
    spec = ReplicaSpec(
        model=dict(max_len=max_seq, **dims),
        seed=0,
        num_heads=dims["num_heads"],
        batch_slots=args.batch_slots,
        max_seq=max_seq,
        kv_layout="paged",
        page_size=8,
        prefill_chunk=8,
        temperature=0.0,
        max_new_tokens=args.max_new_tokens,
        priority_classes=priority_classes,
    )
    requests = synthetic_requests(
        args.requests, vocab_size=dims["vocab_size"],
        max_prompt=args.prompt_len,
        rng=np.random.default_rng(0),
    )
    if class_slos:
        # deal the synthetic traffic across the SLO'd classes round-
        # robin: a class with an SLO but no traffic FAILS by design
        # (an SLO that cannot be demonstrated is not met), which would
        # make every run of this smoke verb exit 1
        classes = sorted(class_slos)
        requests = [
            _dc.replace(r, tenant=classes[i % len(classes)],
                        priority=classes[i % len(classes)])
            for i, r in enumerate(requests)
        ]
    view = observe_fleet(
        spec, requests,
        replicas=args.replicas,
        trace_dir=args.trace_dir,
        faults=args.faults,
        slo=slo,
        class_slos=class_slos,
    )
    report = view["fleet_report"]
    chains_ok = sum(1 for c in view["failover"].values() if c["ok"])
    print(_json.dumps({
        "mode": "fleet",
        "merged_trace": view["merged_trace_path"],
        "replicas": args.replicas,
        "requests": report.requests,
        "replica_deaths": report.replica_deaths,
        "restarts": report.restarts,
        "redeliveries": report.redeliveries,
        "lost_requests": report.lost_requests,
        "failover_chains": len(view["failover"]),
        "failover_chains_ok": chains_ok,
        "fleet_latency": view["fleet_latency"],
        "fleet_latency_per_class": view["fleet_latency_per_class"],
        "flight_recorder_dumps": len(view["flight_recorder_dumps"]),
        "slo": view["slo"],
        "slo_per_tenant": view["slo_per_tenant"],
    }))
    print(
        f"[obs] open {view['merged_trace_path']} in chrome://tracing or "
        "https://ui.perfetto.dev", file=sys.stderr,
    )
    rc = 0
    if view["slo"] is not None and not view["slo"]["pass"]:
        print("[obs] SLO VIOLATED", file=sys.stderr)
        rc = 1
    per_tenant = view["slo_per_tenant"]
    if per_tenant is not None and not per_tenant["pass"]:
        failed = sorted(
            cls for cls, res in per_tenant["per_class"].items()
            if not res["pass"]
        )
        print(f"[obs] per-tenant SLO VIOLATED: {failed}", file=sys.stderr)
        rc = 1
    return rc


def _cmd_tpu(args) -> int:
    import json as _json

    from distributeddeeplearning_tpu.control.submit import Submitter
    from distributeddeeplearning_tpu.control.tpu import list_pods, pod_from_settings

    cfg, runner, registry = _control(args)
    pod = pod_from_settings(cfg, runner)
    if args.tpu_command == "create":
        created = pod.create()
        print(f"TPU {pod.name}: {'created' if created else 'already exists'}")
    elif args.tpu_command == "delete":
        pod.delete()
        print(f"TPU {pod.name}: delete requested")
    elif args.tpu_command == "status":
        meta = pod.describe()
        if meta is None:
            print(f"TPU {pod.name}: not found")
            return 1
        print(_json.dumps(meta, indent=2) if meta else f"TPU {pod.name}: exists")
    elif args.tpu_command == "list":
        for entry in list_pods(runner, cfg.get("GCP_ZONE"),
                               cfg.get("GCP_PROJECT") or None):
            print(entry.get("name", entry))
    elif args.tpu_command == "ssh":
        pod.ssh(args.cmd, worker=args.worker)
    elif args.tpu_command == "bootstrap":
        Submitter(cfg, runner, registry).bootstrap_pod(args.project_dir, pod=pod)
    elif args.tpu_command == "queue":
        rid = pod.request_queued(
            request_id=args.request_id,
            spot=args.spot,
            reserved=args.reserved,
            valid_until_duration=args.valid_until,
        )
        print(f"queued-resource request {rid} filed for TPU {pod.name}")
    elif args.tpu_command == "queue-status":
        state = pod.queued_state(args.request_id)
        if state is None:
            print("no queued-resource request found")
            return 1
        print(state)
    elif args.tpu_command == "queue-delete":
        if pod.delete_queued(args.request_id, force=args.force):
            print("queued-resource request delete requested")
        else:
            print(
                "request is ACTIVE (owns a live node); re-run with --force",
                file=sys.stderr,
            )
            return 1
    return 0


def _cmd_storage(args) -> int:
    from distributeddeeplearning_tpu.control.storage import (
        GcsStorage,
        generate_tfrecords_gated,
    )

    cfg, runner, _ = _control(args)
    verb = args.storage_command
    data_dir = getattr(args, "data_dir", None) or cfg.get("DATA_DIR", "/data")

    if verb == "prepare-imagenet":
        if args.dry_run:
            print(
                f"[dry-run] prepare_imagenet({args.train_tar}, {args.val_tar})"
                f" -> {args.target_dir or cfg.get('DATA_DIR', '/data')}"
            )
            return 0
        from distributeddeeplearning_tpu.data.prepare_imagenet import (
            prepare_imagenet,
        )

        prepare_imagenet(
            args.train_tar,
            args.val_tar,
            args.target_dir or cfg.get("DATA_DIR", "/data"),
            args.val_map,
            check_sha1=not args.no_checksum,
        )
        return 0

    if verb == "build-cache":
        is_training = args.split == "train"
        from distributeddeeplearning_tpu.data.raw_cache import (
            build_raw_cache,
            cache_path_for,
        )

        if not 0 <= args.shard_index < args.shard_count:
            print(
                f"--shard-index {args.shard_index} out of range "
                f"[0, {args.shard_count})", file=sys.stderr,
            )
            return 1
        cache_dir = args.cache_dir or cache_path_for(
            args.data_dir, is_training, args.image_size,
            shard_count=args.shard_count, shard_index=args.shard_index,
        )
        if args.dry_run:
            print(f"[dry-run] build_raw_cache({args.data_dir}) -> {cache_dir}")
            return 0
        manifest = build_raw_cache(
            args.data_dir, cache_dir, is_training, image_size=args.image_size,
            shard_count=args.shard_count, shard_index=args.shard_index,
        )
        size_b = manifest.get(
            "bytes", manifest["count"] * args.image_size**2 * 3
        )
        print(
            f"{cache_dir}: {manifest['count']} images at "
            f"{args.image_size}px ({size_b / 1e9:.1f} GB)"
        )
        return 0

    if verb == "val-maps":
        if args.dry_run:
            print(f"[dry-run] derive_val_maps({args.devkit}) -> {args.out}")
            return 0
        from distributeddeeplearning_tpu.data.val_maps import (
            derive_val_maps,
            write_val_maps,
        )

        digest = write_val_maps(
            derive_val_maps(args.devkit), args.out,
            verify=not args.no_verify,
        )
        print(f"{args.out}: sha256 {digest}")
        return 0

    if verb == "class-index":
        from distributeddeeplearning_tpu.data.class_index import (
            build_nounid_to_class,
            load_class_index,
            verify_class_index,
            write_nounid_to_class,
        )

        image_dir = args.image_dir or f"{data_dir.rstrip('/')}/train"
        if args.dry_run:
            print(f"[dry-run] build_nounid_to_class({image_dir})")
            return 0
        mapping = build_nounid_to_class(image_dir, label_offset=args.label_offset)
        output = args.output or f"{data_dir.rstrip('/')}/imagenet_nounid_to_class.json"
        write_nounid_to_class(mapping, output)
        print(f"wrote {len(mapping)}-class mapping to {output}")
        if args.verify:
            verify_path = args.verify
            if verify_path == "shipped":
                from distributeddeeplearning_tpu.data.class_index import (
                    shipped_class_index_path,
                )

                verify_path = str(shipped_class_index_path())
            problems = verify_class_index(
                load_class_index(verify_path), mapping,
                label_offset=args.label_offset,
            )
            if problems:
                for p in problems[:20]:
                    print(f"MISMATCH: {p}", file=sys.stderr)
                return 1
            print(f"verified against {verify_path}: OK")
        return 0

    if verb == "generate-tfrecords":
        image_dir = args.image_dir or cfg.get("DATA_DIR", "/data")
        output_dir = args.output_dir or f"{image_dir.rstrip('/')}/tfrecords"
        if args.dry_run:
            print(f"[dry-run] generate_tfrecords({image_dir}) -> {output_dir}")
            return 0
        kwargs = {}
        if args.train_shards:
            kwargs["train_shards"] = args.train_shards
        if args.validation_shards:
            kwargs["validation_shards"] = args.validation_shards
        counts = generate_tfrecords_gated(
            image_dir, output_dir, force=args.force, **kwargs
        )
        print(f"wrote {counts} records to {output_dir}")
        return 0

    storage = GcsStorage(
        runner,
        bucket=cfg.get("GCS_BUCKET"),
        project=cfg.get("GCP_PROJECT") or None,
        location=cfg.get("REGION") or None,
    )
    if verb == "create-bucket":
        created = storage.ensure_bucket(cfg)
        print(f"bucket {storage.url}: {'created' if created else 'already exists'}")
    elif verb == "upload-images":
        storage.upload_images(data_dir)
    elif verb == "download-images":
        storage.download_images(data_dir)
    elif verb == "upload-tfrecords":
        storage.upload_tfrecords(f"{data_dir.rstrip('/')}/tfrecords")
    elif verb == "download-tfrecords":
        storage.download_tfrecords(f"{data_dir.rstrip('/')}/tfrecords")
    return 0


def _cmd_tensorboard(args) -> int:
    """Point TensorBoard at run logdirs (``inv tensorboard`` role).

    ``--run`` resolves the dir recorded at submit time — a ``gs://`` dir
    for remote runs, so a RUNNING pod job's scalars stream live (the
    reference's azureml.tensorboard role); local runs resolve to the
    registry tree."""
    cfg, runner, registry = _control(args)
    # same default the submit paths register runs under
    experiment = args.experiment or cfg.get("EXPERIMENT_NAME") or "experiment"
    if args.run:
        record = registry.find(experiment, args.run)
        logdir = (record.extra.get("tensorboard_dir") if record else None) or (
            str(registry.root / experiment / args.run / "tb")
        )
    else:
        logdir = str(registry.root / experiment)
    runner.run(
        ["tensorboard", "--logdir", logdir, "--port", str(args.port)],
        capture=False,
        check=False,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
