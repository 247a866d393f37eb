"""Subprocess worker for the two-process multi-host seam test.

Run as: python multihost_worker.py <coord_port> <process_id> <num_processes>
        <local_device_count> [tfrecord_dir]

Exercises, under a REAL two-process ``jax.distributed`` rendezvous on the
CPU backend (the regime CI's single-process virtual mesh cannot reach):

1. ``parallel.distributed.initialize``'s explicit-rendezvous branch;
2. ``parallel.sharding.shard_batch``'s
   ``jax.make_array_from_process_local_data`` path, with a position-weighted
   fingerprint so a wrong global row order fails, not just wrong values;
3. ``data.tfrecords.input_fn``'s shard defaulting from the process topology
   (the TPU-native ``dataset.shard(hvd.size(), hvd.rank())``): the two
   hosts' label multisets must be disjoint and union to the full dataset.

Prints one line per passed stage; the parent asserts on them.
"""

from __future__ import annotations

import os
import sys


def main() -> int:
    coord_port, pid, nprocs, local_devices = (
        sys.argv[1],
        int(sys.argv[2]),
        int(sys.argv[3]),
        int(sys.argv[4]),
    )
    tfrecord_dir = sys.argv[5] if len(sys.argv) > 5 else None

    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={local_devices}".strip()
    )
    import jax

    # the CPU pod, pinned before the first backend query (the
    # tests/conftest.py recipe) whatever environment the worker inherited
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.parallel.distributed import initialize
    from distributeddeeplearning_tpu.parallel.sharding import (
        replicated,
        shard_batch,
    )

    ctx = initialize(
        coordinator_address=f"127.0.0.1:{coord_port}",
        num_processes=nprocs,
        process_id=pid,
        force=True,
    )
    assert ctx.process_count == nprocs, ctx
    assert ctx.local_device_count == local_devices, ctx
    print(f"WORKER {pid} STAGE rendezvous OK", flush=True)

    mesh = create_mesh(MeshSpec())
    n_global = mesh.devices.size
    assert n_global == nprocs * local_devices
    global_batch = 2 * n_global
    full = np.arange(global_batch * 3, dtype=np.float32).reshape(global_batch, 3)
    per_host = global_batch // nprocs
    local = full[pid * per_host : (pid + 1) * per_host]

    batch = shard_batch(mesh, {"x": local})
    leaf = batch["x"]
    assert leaf.shape == (global_batch, 3), leaf.shape

    import jax.numpy as jnp

    def fingerprint(b):
        # position-dependent weights: permuted global row order changes the sum
        w = (jnp.arange(global_batch, dtype=jnp.float32) + 1.0)[:, None]
        return (b["x"] * w).sum()

    got = float(jax.jit(fingerprint, out_shardings=replicated(mesh))(batch))
    expected = float(
        (full * (np.arange(global_batch, dtype=np.float32) + 1.0)[:, None]).sum()
    )
    assert abs(got - expected) <= 1e-3 * abs(expected), (got, expected)
    print(f"WORKER {pid} STAGE shard_batch OK fingerprint={got}", flush=True)

    if tfrecord_dir:
        from jax.experimental import multihost_utils

        from distributeddeeplearning_tpu.data import tfrecords

        # No explicit shard_count/shard_index: must default to the process
        # topology (data/tfrecords.py input_fn).
        labels = np.concatenate(
            [
                b["label"]
                for b in tfrecords.input_fn(
                    tfrecord_dir,
                    False,
                    batch_size=2,
                    num_shards=4,
                    image_size=32,
                    repeat=False,
                )
            ]
        )
        # Fixed-size exchange: each host's shard is 2 of 4 files = 6 records.
        assert labels.shape == (6,), labels.shape
        gathered = multihost_utils.process_allgather(labels)
        combined = sorted(np.asarray(gathered).reshape(-1).tolist())
        assert combined == sorted([1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3]), combined
        mine = sorted(labels.tolist())
        other = sorted(
            np.asarray(gathered).reshape(nprocs, -1)[1 - pid].tolist()
        )
        assert mine != other or len(set(combined)) == 1
        print(f"WORKER {pid} STAGE host_file_sharding OK", flush=True)

    print(f"WORKER {pid} DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
