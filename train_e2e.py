"""Fed-path end-to-end training artifact driver (``TRAIN_E2E_r{N}.json``).

The one composition ``bench.py`` never proves: the FULL ``Trainer`` —
``workloads/imagenet.main``, the reference's flagship path
(``TensorFlow_imagenet/src/resnet_main.py:282-307``) — fed from a REAL
record pipeline at bench batch size, with eval every epoch, a mid-run
checkpoint+resume (fit is invoked twice; the second run must continue from
the first's checkpoint, not restart), and the per-epoch metrics JSONL.

Data is the deterministic 4096-image synthetic-JPEG TFRecord shard set
(``data/bench_data.py``, reference converter schema) consumed through the
decode-once uint8 raw cache (``data/raw_cache.py``) — the input pipeline
built to feed a chip from a decode-bound host (its rates are not measured
on today's code: ROADMAP S6).

Prints ONE JSON line and writes it to ``TRAIN_E2E_r{round}.json``:
fed images/sec per epoch, the staged-consume ceiling it should approach on
a real TPU-VM, final train/eval metrics, and the resume evidence.

Labels are synthetic (1 + i mod 1000 over random JPEGs), so accuracy only
measures that the label plumbing learns SOMETHING (train top-1 must move
off the 0.001 floor by memorization); convergence quality is
``tests/test_convergence.py``'s job on real 3-class data.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--round", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=3,
                    help="total epochs; the first runs in invocation 1, "
                    "the rest resume in invocation 2")
    ap.add_argument("--train-images", type=int, default=4096)
    ap.add_argument("--val-images", type=int, default=512)
    ap.add_argument("--data-dir", default=None,
                    help="shard location (default: ~/.cache/ddlt/bench-shards)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default TRAIN_E2E_r{round:02d}.json)")
    ap.add_argument("--keep-workdir", action="store_true")
    args = ap.parse_args()

    from distributeddeeplearning_tpu.data.bench_data import (
        ensure_bench_shards,
        generate_bench_shards,
    )
    from distributeddeeplearning_tpu.workloads.imagenet import main as train_main

    train_dir = ensure_bench_shards(
        args.data_dir, num_images=args.train_images, num_shards=8
    )
    val_dir = os.path.join(os.path.dirname(train_dir), "bench-shards-val")
    generate_bench_shards(
        val_dir, num_images=args.val_images, num_shards=2, split="validation"
    )

    work = tempfile.mkdtemp(prefix="ddlt-e2e-")
    ckpt = os.path.join(work, "ckpt")
    jsonl = os.path.join(work, "metrics.jsonl")
    steps_per_epoch = args.train_images // args.batch_size
    common = dict(
        model="resnet50",
        data_format="tfrecords",
        input_pipeline="raw",
        training_data_path=train_dir,
        validation_data_path=val_dir,
        batch_size=args.batch_size,
        train_images=args.train_images,
        steps_per_epoch=steps_per_epoch,
        warmup_epochs=1,
        save_filepath=ckpt,
        metrics_path=jsonl,
        checkpoint_every_steps=max(steps_per_epoch // 2, 1),  # mid-epoch saves
        seed=42,
    )

    # Invocation 1: first epoch, then "the job dies".
    state1, fit1 = train_main(epochs=1, resume=False, **common)
    steps_after_1 = int(state1.step)

    # Invocation 2: same config, more epochs — MUST resume, not restart.
    state2, fit2 = train_main(epochs=args.epochs, resume=True, **common)
    steps_after_2 = int(state2.step)
    resumed = steps_after_2 == args.epochs * steps_per_epoch and (
        fit2.epochs_run == args.epochs - 1
    )

    rows = []
    with open(jsonl) as f:
        for line in f:
            if line.strip():
                rows.append(json.loads(line))
    epoch_rows = [r for r in rows if "images_per_second" in r]
    steady = [
        r["images_per_second"]
        for r in epoch_rows
        if not r.get("includes_compile")
    ] or [r["images_per_second"] for r in epoch_rows]
    fed_img_sec = sorted(steady)[len(steady) // 2]

    result = {
        "metric": "resnet50_e2e_fed_train_img_sec",
        "value": round(fed_img_sec, 1),
        "unit": "img/sec",
        "vs_baseline": None,
        "round": args.round,
        "harness": (
            "python train_e2e.py — full Trainer.fit (workloads/imagenet.main),"
            " tfrecords->raw-cache pipeline, eval every epoch, two invocations"
            " with checkpoint+resume between them"
        ),
        "batch_size": args.batch_size,
        "steps_per_epoch": steps_per_epoch,
        "epochs_total": args.epochs,
        "resume_proof": {
            "steps_after_first_invocation": steps_after_1,
            "steps_after_second_invocation": steps_after_2,
            "epochs_run_in_second_invocation": fit2.epochs_run,
            "resumed_not_restarted": resumed,
        },
        "final_train_metrics": {
            k: float(v) for k, v in (fit2.final_train_metrics or {}).items()
        },
        "final_eval_metrics": {
            k: float(v) for k, v in (fit2.final_eval_metrics or {}).items()
        },
        "per_epoch_images_per_second": [
            round(r["images_per_second"], 1) for r in epoch_rows
        ],
        "staged_consume_ceiling_note": (
            "compare with `bench.py --data --input-pipeline raw` on the "
            "same machine: its staged rate is the ceiling the fed rate "
            "should approach, its host rate the pipeline alone"
        ),
        "labels_note": "synthetic labels (1+i mod 1000); accuracy proves "
        "plumbing/memorization, not convergence (see tests/test_convergence)",
    }
    if not resumed:
        result["error"] = "second invocation did not resume from checkpoint"
    out = args.out or f"TRAIN_E2E_r{args.round:02d}.json"
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    if not args.keep_workdir:
        shutil.rmtree(work, ignore_errors=True)
    return 0 if resumed else 1


if __name__ == "__main__":
    sys.exit(main())
