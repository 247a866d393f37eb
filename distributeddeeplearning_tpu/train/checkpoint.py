"""Sharded checkpoint / resume with DURABLE, verified generations.

The reference's three partial mechanisms (SURVEY.md §5 "Checkpoint / resume"):
TF Estimator implicit rank-0 checkpoints (``resnet_main.py:140-158``), a buggy
PyTorch rank-0 epoch save (``imagenet_pytorch_horovod.py:257-260`` — NameError
off rank 0), and a full resume protocol stranded in dead code
(``PyTorch_hvd/src/imagenet_pytorch_horovod.py:62-72,133-144``).

TPU-native replacement: orbax ``CheckpointManager`` writes the train-state
pytree **sharded** — every host writes its own param shards in parallel (no
rank-0 gather, no broadcast), and restore places shards directly onto the
mesh from the target state's shardings.

Durability layer (PR 13) — storage is not trusted:

- **verified saves**: every generation gets a content MANIFEST
  (:data:`MANIFEST_NAME` — per-leaf CRC32 + shape + dtype over the saved
  items) written atomically (tmp + rename) only AFTER orbax finalizes the
  generation's data.  A generation without a valid manifest is
  by-construction incomplete (a torn write, a writer killed mid-commit)
  and never restore-eligible;
- **corruption-tolerant restore**: :meth:`Checkpointer.restore` /
  :meth:`Checkpointer.restore_params` walk generations newest-first,
  verify each candidate against its manifest, and FALL BACK past any
  generation that fails to read or to verify — with an obs event, a
  ``ckpt.verify_failures`` counter bump and a flight-recorder dump naming
  the generation and the first failing leaf.  A corrupt latest costs one
  generation of progress, not the run;
- :meth:`Checkpointer.latest_verified_step` replaces the blind
  ``latest_step()`` everywhere a resume decision is made (trainer
  rollback, the ``ddlt train`` supervisor's accounting, serve startup);
- **params-only item**: generations are saved as TWO orbax items —
  ``params`` and ``state`` (step / opt_state / batch_stats) — so
  ``restore_params`` (the ``ddlt serve`` startup path) reads only the
  params bytes instead of ~3x that for an AdamW checkpoint.  Generations
  from before this layout (single ``default`` item, no manifest) keep
  working through the legacy full-read path.

Deterministic chaos for all of it: ``DDLT_FAULTS`` kinds ``ckpt_corrupt``
(flip / truncate / unlink / manifest) and ``ckpt_torn`` fire at generation
finalize (:mod:`..utils.faults`), exercised by ``bench.py --ckpt-faults``
and ``tests/test_checkpoint.py``.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from distributeddeeplearning_tpu.obs import goodput as _goodput
from distributeddeeplearning_tpu.obs.recorder import get_recorder
from distributeddeeplearning_tpu.obs.registry import get_registry
from distributeddeeplearning_tpu.obs.trace import get_tracer
from distributeddeeplearning_tpu.utils import faults as faults_mod
from distributeddeeplearning_tpu.utils.retry import retry_call

logger = logging.getLogger("ddlt.checkpoint")

PyTree = Any

#: per-generation content manifest, written into the finalized step dir
MANIFEST_NAME = "ddlt_manifest.json"
#: directory-level marker: once ANY manifest has been committed here, a
#: manifest-less generation is incomplete — never "legacy"
DURABLE_MARKER = "ddlt_durable.json"
MANIFEST_FORMAT = 1

CORRUPT_MODES = ("flip", "truncate", "unlink", "manifest")


class CheckpointCorruptionError(RuntimeError):
    """Every manifested generation failed verification — nothing left to
    fall back to.  Deliberately NOT restartable: a supervisor restart
    would re-read the same corrupt store forever."""


# -- manifest construction / verification ----------------------------------


def _leaf_entries(prefix: str, tree: PyTree) -> Dict[str, Dict[str, Any]]:
    """``"<item>/<keypath>" -> {shape, dtype, crc32}`` for every leaf.

    CRC32 over the host bytes: fast enough to stay inside the <10%%
    verify-overhead budget (zlib runs at memory bandwidth next to the
    serialize the save already pays), strong enough to catch the bit-flip
    / truncation / wrong-leaf classes the manifest exists for.
    """
    entries: Dict[str, Dict[str, Any]] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.ascontiguousarray(np.asarray(leaf))
        entries[f"{prefix}{jax.tree_util.keystr(path)}"] = {
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "crc32": zlib.crc32(arr),
        }
    return entries


def build_manifest(step: int, items: Dict[str, PyTree]) -> Dict[str, Any]:
    """Content manifest over the generation's items (host-side arrays)."""
    leaves: Dict[str, Dict[str, Any]] = {}
    for item_name in sorted(items):
        leaves.update(_leaf_entries(f"{item_name}/", items[item_name]))
    return {
        "format": MANIFEST_FORMAT,
        "step": int(step),
        "created_unix_s": time.time(),
        "items": sorted(items),
        "leaves": leaves,
    }


class _PendingManifest:
    """A generation's manifest being built in the BACKGROUND.

    ``save()`` snapshots the arrays to host synchronously as PRIVATE
    COPIES — ``np.array(copy=True)``, never ``device_get``: on the CPU
    backend device_get returns zero-copy VIEWS of the jax buffers, and
    the very next donated train step reuses that memory in place, so a
    background hash over a view would checksum clobbered bytes (a bug
    the chaos bench caught live) — and hands the checksum work to a
    thread.  The CRC pass rides the same async window the orbax write
    does, so the save path pays one memcpy + thread spawn, not the hash.
    ``wall_s`` records the thread's own CPU-side wall for the artifact's
    accounting; the save-path overhead gate counts only what
    :class:`Checkpointer` adds synchronously (plus any join wait at
    finalize, which a write slower than the hash absorbs to ~0).
    """

    def __init__(self, step: int, host_items: Dict[str, PyTree]):
        self.step = step
        self.manifest: Optional[Dict[str, Any]] = None
        self.wall_s = 0.0
        self._thread = threading.Thread(
            target=self._build, args=(step, host_items),
            name=f"ddlt-ckpt-manifest-{step}", daemon=True,
        )
        self._thread.start()

    def _build(self, step: int, host_items: Dict[str, PyTree]) -> None:
        t0 = time.perf_counter()
        self.manifest = build_manifest(step, host_items)
        self.wall_s = time.perf_counter() - t0

    def join(self) -> Optional[Dict[str, Any]]:
        self._thread.join()
        return self.manifest


def verify_manifest(
    manifest: Dict[str, Any], items: Dict[str, PyTree]
) -> List[str]:
    """Check restored ``items`` against their manifest entries.

    Returns problem strings (empty = verified).  Only the items actually
    restored are checked — a params-only restore verifies the ``params/``
    subset — but a restored item must cover its manifest entries exactly:
    a missing or extra leaf is structural corruption, not a skip.
    """
    problems: List[str] = []
    expected = manifest.get("leaves")
    if not isinstance(expected, dict) or not expected:
        return ["manifest carries no leaf entries"]
    got: Dict[str, Dict[str, Any]] = {}
    for item_name in sorted(items):
        got.update(_leaf_entries(f"{item_name}/", items[item_name]))
    prefixes = tuple(f"{name}/" for name in items)
    for name, entry in sorted(expected.items()):
        if not name.startswith(prefixes):
            continue  # an item this restore did not read
        actual = got.pop(name, None)
        if actual is None:
            problems.append(f"leaf {name} missing from the restored tree")
        elif actual != entry:
            problems.append(
                f"leaf {name} mismatch (manifest {entry}, restored {actual})"
            )
    for name in sorted(got):
        problems.append(f"restored leaf {name} not named by the manifest")
    return problems


def _atomic_write_json(path: Path, payload: Dict[str, Any]) -> None:
    """Write-then-rename so a reader can never observe a torn manifest —
    the manifest's own durability must be at least as good as the
    property it certifies."""
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def load_manifest(step_dir: Path) -> Optional[Dict[str, Any]]:
    """The generation's manifest, or None when missing/unparseable/
    structurally invalid (all three mean: not restore-eligible)."""
    path = Path(step_dir) / MANIFEST_NAME
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if (
        not isinstance(manifest, dict)
        or manifest.get("format") != MANIFEST_FORMAT
        or not isinstance(manifest.get("leaves"), dict)
        or not manifest["leaves"]
    ):
        return None
    return manifest


def _data_files(step_dir: Path) -> List[Path]:
    """The generation's data files, largest first (path tiebreak) — the
    deterministic corruption targets.  The manifest and orbax's own
    metadata markers are excluded: ``mode=flip`` must hit ARRAY bytes."""
    files = [
        p
        for p in sorted(Path(step_dir).rglob("*"))
        if p.is_file()
        and p.name != MANIFEST_NAME
        and p.parent.name == "d"  # ocdbt data dirs hold the array bytes
    ]
    return sorted(files, key=lambda p: (-p.stat().st_size, str(p)))


def corrupt_generation(step_dir, mode: str = "flip") -> str:
    """Deterministically corrupt one finalized generation (chaos only).

    Returns a description of what was done.  ``flip`` flips one byte in
    the middle of the largest data file, ``truncate`` halves it,
    ``unlink`` deletes it, ``manifest`` deletes the manifest itself (the
    torn-manifest case: data fine, generation still not restore-eligible).
    """
    step_dir = Path(step_dir)
    if mode not in CORRUPT_MODES:
        raise ValueError(
            f"unknown corruption mode {mode!r}; known: {CORRUPT_MODES}"
        )
    if mode == "manifest":
        (step_dir / MANIFEST_NAME).unlink(missing_ok=True)
        return f"unlinked {MANIFEST_NAME}"
    targets = _data_files(step_dir)
    if not targets:
        raise FileNotFoundError(f"no data files under {step_dir}")
    target = targets[0]
    if mode == "unlink":
        target.unlink()
        return f"unlinked {target.name}"
    if mode == "truncate":
        size = target.stat().st_size
        with open(target, "r+b") as f:
            f.truncate(size // 2)
        return f"truncated {target.name} {size} -> {size // 2} bytes"
    size = target.stat().st_size
    with open(target, "r+b") as f:
        f.seek(size // 2)
        byte = f.read(1)
        f.seek(size // 2)
        f.write(bytes([byte[0] ^ 0xFF]))
    return f"flipped byte {size // 2} of {target.name}"


def latest_verified_step_in_dir(directory) -> Optional[int]:
    """Manager-free scan: newest step whose generation carries a valid
    manifest.  Legacy directories (no durability marker AND no manifest
    anywhere) fall back to the newest step dir — pre-manifest checkpoints
    stay usable.  The ``ddlt train`` supervisor's recovery accounting
    uses this (a full ``Checkpointer`` per restart would be waste)."""
    directory = Path(directory)
    if not directory.exists():
        return None
    steps = sorted(
        (int(p.name) for p in directory.iterdir() if p.name.isdigit()),
        reverse=True,
    )
    if not steps:
        return None
    verified = [
        s for s in steps if load_manifest(directory / str(s)) is not None
    ]
    if verified:
        return verified[0]
    if (directory / DURABLE_MARKER).exists():
        return None  # durable dir with zero verified generations
    return steps[0]  # legacy (pre-manifest) directory


def verified_param_shapes(directory) -> Optional[Dict[str, Tuple[int, ...]]]:
    """``keystr -> shape`` of the ``params`` item in the newest generation
    that carries a valid manifest, read from the manifest alone — no
    restore, no device.  The fleet router validates prompts against the
    model's vocab and position table with this while its workers (which
    each own a chip) do the real restores.  None when no generation has
    a manifest."""
    directory = Path(directory)
    step = latest_verified_step_in_dir(directory)
    manifest = (
        load_manifest(directory / str(step)) if step is not None else None
    )
    if manifest is None:
        return None
    return {
        name[len("params/"):]: tuple(entry["shape"])
        for name, entry in manifest["leaves"].items()
        if name.startswith("params/")
    }


class Checkpointer:
    """Epoch/step-granular sharded checkpointing of a ``TrainState``.

    Only array fields travel (step, params, opt_state, batch_stats); static
    fields (apply_fn, tx) are re-supplied by the restore template, which is
    also the source of target shardings.

    Generations are saved as two orbax items — ``params`` and ``state`` —
    and certified by a per-generation manifest (module docstring).
    :attr:`save_wall_s` / :attr:`snapshot_wall_s` / :attr:`verify_wall_s`
    / :attr:`verify_cpu_s` accumulate the save-path wall, the
    donation-safety memcpy any correct async save pays, the wall
    verification proper ADDED (finalize joins + restore-side manifest
    checks), and the background checksum work that overlapped the async
    write — ``bench.py --ckpt-faults`` gates the verification wall at
    < 10% of the persist wall.
    """

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 5,
        save_interval_steps: int = 1,
        async_save: bool = True,
    ):
        """``async_save`` (the TPU-native default): ``save()`` snapshots
        the state to PRIVATE host copies synchronously, then orbax
        serializes/writes the snapshot in a background thread — the step
        loop never stalls on storage.  Safe with donated train states
        because the snapshot is a real memcpy, not a view (see
        :meth:`_snapshot_items` for the CPU-backend aliasing bug the
        copy kills).  ``wait()``/``close()`` drain pending writes AND
        commit the drained generations' manifests (a manifest may only
        ever cover data that has fully landed)."""
        self.directory = Path(directory).absolute()
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                create=True,
                enable_async_checkpointing=async_save,
            ),
        )
        # manifests awaiting their generation's async finalize, oldest
        # first: step -> background manifest build over the host snapshot
        # taken at save time (BEFORE donation can touch the buffers)
        self._pending_manifests: Dict[int, _PendingManifest] = {}
        # cumulative walls for the verify-overhead gate:
        # - snapshot_wall_s: the private host memcpy a CORRECT async
        #   save needs with donated states regardless of manifests
        #   (see _snapshot_items — without it the background write
        #   aliases the donated buffer);
        # - verify_wall_s: wall ADDED by verification proper (finalize
        #   joins + restore-side manifest checks);
        # - verify_cpu_s: the background checksum work that overlapped
        #   the async write (CPU cost, not save-path wall).
        self.save_wall_s = 0.0
        self.snapshot_wall_s = 0.0
        self.verify_wall_s = 0.0
        self.verify_cpu_s = 0.0

    @staticmethod
    def _state_items(state) -> Dict[str, PyTree]:
        """The two saved items: ``params`` alone (the serve startup read)
        and ``state`` (everything else a resume needs)."""
        return {
            "params": state.params,
            "state": {
                "step": state.step,
                "opt_state": state.opt_state,
                "batch_stats": state.batch_stats,
            },
        }

    def _step_dir(self, step: int) -> Path:
        return self.directory / str(step)

    def _is_composite(self, step: int) -> bool:
        """Post-PR generations carry a ``params`` item dir; legacy ones
        hold the whole tree under orbax's ``default`` item."""
        return (self._step_dir(step) / "params").exists()

    # -- saving ------------------------------------------------------------

    @staticmethod
    def _snapshot_items(items: Dict[str, PyTree]) -> Optional[Dict[str, PyTree]]:
        """PRIVATE host copies of every leaf (``np.array(copy=True)``),
        or None when a leaf is not fully addressable (a true multi-host
        sharded array — each host holds only its shards, so there is no
        local array to copy).

        The snapshot is what gets handed to orbax AND hashed into the
        manifest.  Two bugs die here, both caught live by the chaos
        bench on the CPU backend, where device→host "copies" of jax
        arrays are zero-copy VIEWS of the device buffer:

        - orbax's async serializer read the view in the background while
          the next DONATED train steps reused the buffer in place — a
          checkpoint labeled step N could contain step N+1's bytes
          (restore "succeeded" with silently wrong state);
        - a manifest hashed over the same view checksummed whatever the
          buffer held by hash time.

        One real memcpy at save time makes the written bytes, the
        manifest bytes and the step-N state the same thing by
        construction.
        """
        leaves = jax.tree_util.tree_leaves(items)
        if not all(
            getattr(leaf, "is_fully_addressable", True) for leaf in leaves
        ):
            return None
        return jax.tree_util.tree_map(
            lambda a: np.array(a, copy=True), items
        )

    def save(self, step: int, state, *, deadline_s: Optional[float] = None) -> bool:
        """Save if the manager's policy wants this step. Returns True if saved.

        Transient storage errors are retried with bounded jittered backoff
        (``utils/retry.py``) before propagating; ``deadline_s`` bounds the
        whole attempt+retry sequence on the wall clock — the emergency-
        checkpoint path passes the preemption grace window's remainder so
        backoff can never sleep past the SIGKILL.  The ``checkpoint.save``
        fault-injection site (``utils/faults.py``) exercises this path.
        """
        items = self._state_items(state)
        t0 = time.perf_counter()
        # snapshot FIRST (donation safety — see _snapshot_items); orbax
        # serializes the snapshot, the manifest hashes the same snapshot
        v0 = time.perf_counter()
        snapshot = self._snapshot_items(items)
        self.snapshot_wall_s += time.perf_counter() - v0
        to_save = snapshot if snapshot is not None else items
        if snapshot is None:
            # true multi-host sharded state: orbax's per-host sharded
            # write takes over; per-host manifests are future work, so
            # the generation ships uncertified (legacy restore semantics)
            logger.warning(
                "step %d: non-addressable sharded state — saving without "
                "a content manifest (multi-host manifests not yet "
                "supported)", step,
            )

        def _save() -> bool:
            faults_mod.get_plan().maybe_io_error("checkpoint.save")
            return self._mgr.save(
                step,
                args=ocp.args.Composite(
                    **{
                        name: ocp.args.StandardSave(tree)
                        for name, tree in to_save.items()
                    }
                ),
            )

        with get_tracer().span("ckpt/save", step=step):
            saved = retry_call(
                _save, retries=2, base_delay=0.2, max_delay=2.0,
                description=f"checkpoint save (step {step})",
                deadline_s=deadline_s,
            )
            if saved and snapshot is not None:
                # checksum in the background over the SAME private
                # snapshot orbax is writing — the hash overlaps the
                # async write, and the manifest WRITE is deferred until
                # the generation's data has landed (_finalize_manifests)
                # so a manifest can never certify a torn generation
                self._pending_manifests[step] = _PendingManifest(
                    step, snapshot
                )
            # orbax serializes async saves: initiating THIS save waited
            # for the previous generation's commit, so every pending
            # manifest except this step's is ready to finalize now
            self._finalize_manifests(exclude_step=step)
        self.save_wall_s += time.perf_counter() - t0
        # goodput detail: the trainer's marks already charge this wall to
        # checkpoint_blocking — the note splits it save-join vs wait-drain
        # for the ledger's notes block (never double-counted in the sum)
        _goodput.get_ledger().note(
            "ckpt_save_block_s", time.perf_counter() - t0
        )
        if saved:
            logger.info("checkpoint saved at step %d -> %s", step, self.directory)
        return saved

    def _finalize_manifests(self, exclude_step: Optional[int] = None) -> None:
        """Commit manifests for every pending generation whose data has
        landed (final step dir present — orbax renames the tmp dir only
        after the commit completes).  Also the injection point for the
        ``ckpt_torn`` / ``ckpt_corrupt`` chaos kinds: both model failures
        that strike exactly here, at generation finalize."""
        plan = faults_mod.get_plan()
        for step in sorted(self._pending_manifests):
            if step == exclude_step:
                continue
            pending = self._pending_manifests.pop(step)
            step_dir = self._step_dir(step)
            if not step_dir.exists():
                if any(
                    self.directory.glob(f"{step}.orbax-checkpoint-tmp-*")
                ):
                    # STILL IN FLIGHT: a policy-skipped save() reaches
                    # here without orbax having waited for the previous
                    # generation's commit — keep the manifest pending for
                    # the next save()/wait() instead of permanently
                    # un-certifying a write that will land fine
                    self._pending_manifests[step] = pending
                    continue
                # evicted (max_to_keep) before its manifest committed, or
                # the write never landed — either way nothing to certify
                logger.debug(
                    "generation %d gone before manifest commit", step
                )
                continue
            # join the background checksum: with a write slower than the
            # hash (the normal case) this is a no-op wait; either way the
            # join wall is charged as verify overhead on the save path
            v0 = time.perf_counter()
            manifest = pending.join()
            self.verify_wall_s += time.perf_counter() - v0
            self.verify_cpu_s += pending.wall_s
            if manifest is None:  # pragma: no cover — build thread died
                logger.warning(
                    "manifest build failed for generation %d — generation "
                    "left uncertified", step,
                )
                continue
            if plan and plan.take_ckpt_torn():
                # writer "dies" mid-generation: data torn, no manifest —
                # the generation must read as incomplete forever
                try:
                    corrupt_generation(step_dir, "truncate")
                except (OSError, FileNotFoundError):  # pragma: no cover
                    pass
                continue
            try:
                _atomic_write_json(step_dir / MANIFEST_NAME, manifest)
                marker = self.directory / DURABLE_MARKER
                if not marker.exists():
                    _atomic_write_json(
                        marker, {"manifest_format": MANIFEST_FORMAT}
                    )
            except OSError as exc:
                # an uncertified-but-complete generation is merely not
                # restore-eligible; failing the RUN over it would invert
                # the durability story
                logger.warning(
                    "manifest write failed for generation %d: %s", step, exc
                )
                continue
            options = plan.take_ckpt_corrupt() if plan else None
            if options is not None:
                what = corrupt_generation(
                    step_dir, str(options.get("mode", "flip"))
                )
                logger.warning(
                    "ckpt_corrupt: generation %d — %s", step, what
                )

    def wait(self, *, deadline_s: Optional[float] = None) -> None:
        """Drain pending async saves, retrying transient storage failures
        (same policy as :meth:`save`), then commit the drained
        generations' manifests.  ``deadline_s`` bounds the retry backoff —
        the emergency-checkpoint path calls this synchronously inside the
        preemption grace window."""

        def _wait() -> None:
            faults_mod.get_plan().maybe_io_error("checkpoint.wait")
            self._mgr.wait_until_finished()

        t0 = time.perf_counter()
        retry_call(
            _wait, retries=2, base_delay=0.2, max_delay=2.0,
            description="checkpoint wait", deadline_s=deadline_s,
        )
        self._finalize_manifests()
        # goodput detail note (see save(): categories come from the
        # trainer's marks, this is the save-join vs wait-drain split)
        _goodput.get_ledger().note(
            "ckpt_wait_block_s", time.perf_counter() - t0
        )

    # -- restore-eligibility ----------------------------------------------

    def latest_step(self) -> Optional[int]:
        """Newest step orbax knows about — storage-trusting; resume
        decisions should use :meth:`latest_verified_step`."""
        return self._mgr.latest_step()

    def all_steps(self) -> List[int]:
        return sorted(int(s) for s in self._mgr.all_steps())

    def _is_legacy_dir(self, steps: List[int]) -> bool:
        """Pre-manifest directory: no durability marker and no manifest on
        any generation — trust the newest step like the old code did."""
        if (self.directory / DURABLE_MARKER).exists():
            return False
        return not any(
            load_manifest(self._step_dir(s)) is not None for s in steps
        )

    def latest_verified_step(self) -> Optional[int]:
        """Newest step whose generation carries a valid manifest — the
        restore-eligibility decision every resume path keys off.  Legacy
        (pre-manifest) directories fall back to ``latest_step`` with a
        warning so old checkpoints stay usable.

        This is a MANIFEST-level probe (cheap: one JSON read per
        generation); full content verification needs the data bytes and
        happens inside the restore walk — a data-corrupt generation
        whose manifest survived intact reads as eligible here and is
        discovered (and, on the trainer path, evicted) at restore time,
        so accounting built on this probe can run one generation ahead
        of where a restart actually lands until that restore runs."""
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            return None
        for step in steps:
            if load_manifest(self._step_dir(step)) is not None:
                return step
        if self._is_legacy_dir(steps):
            logger.warning(
                "checkpoint dir %s has no manifests (pre-durability "
                "layout) — trusting latest step %d unverified",
                self.directory, steps[0],
            )
            return steps[0]
        return None

    # -- restore -----------------------------------------------------------

    def _note_verify_failure(
        self, step: int, why: str, leaf: Optional[str]
    ) -> None:
        """One verification failure = one obs event + counter bump + a
        flight-recorder dump naming the generation and leaf — the
        operator-facing answer to "why did resume go backwards?"."""
        logger.error(
            "checkpoint generation %d FAILED verification (%s) — "
            "falling back to the newest older verified generation",
            step, why,
        )
        get_tracer().event(
            "ckpt/verify_failed", cat="ckpt", step=step, why=why, leaf=leaf,
        )
        get_registry().counter("ckpt.verify_failures").inc()
        get_recorder().dump(
            "ckpt_verify_failed", registry=get_registry(),
            generation=step, why=why, leaf=leaf,
            directory=str(self.directory),
        )

    def _restore_items(
        self, step: int, abstract_items: Optional[Dict[str, PyTree]]
    ) -> Dict[str, PyTree]:
        """Read one generation's items (composite or legacy layout) into
        the abstract templates (None = as-saved, host-resident)."""
        if self._is_composite(step):
            names = (
                sorted(abstract_items)
                if abstract_items is not None
                else ["params", "state"]
            )
            restored = self._mgr.restore(
                step,
                args=ocp.args.Composite(
                    **{
                        name: ocp.args.StandardRestore(
                            abstract_items[name]
                            if abstract_items is not None
                            else None
                        )
                        for name in names
                    }
                ),
            )
            return {name: restored[name] for name in names}
        # legacy single-item generation: the whole tree under "default"
        flat = None
        if abstract_items is not None:
            flat = {
                "params": abstract_items["params"],
                **abstract_items["state"],
            }
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(flat)
        )
        return {
            "params": restored["params"],
            "state": {
                "step": restored["step"],
                "opt_state": restored["opt_state"],
                "batch_stats": restored["batch_stats"],
            },
        }

    def _verify_items(
        self, step: int, items: Dict[str, PyTree]
    ) -> bool:
        """True when ``items`` match the generation's manifest; emits the
        failure triplet (event/counter/dump) otherwise."""
        manifest = load_manifest(self._step_dir(step))
        if manifest is None:
            self._note_verify_failure(
                step, "missing or invalid manifest", None
            )
            return False
        with get_tracer().span("ckpt/verify", step=step):
            v0 = time.perf_counter()
            problems = verify_manifest(manifest, items)
            self.verify_wall_s += time.perf_counter() - v0
        if problems:
            first = problems[0]
            leaf = first.split(" ")[1] if first.startswith("leaf ") else None
            self._note_verify_failure(
                step, "; ".join(problems[:3]), leaf
            )
            return False
        return True

    def _verified_candidates(self, steps: List[int]):
        """Newest-first steps whose manifests parse (the restore walk
        order) plus the REJECTED steps.  Manifest-less generations in a
        durable dir are rejected with the failure triplet (they are the
        torn-write signature) — EXCEPT generations this instance knows
        are merely pending their manifest commit (async save not yet
        drained): the writer's own restore racing its own in-flight save
        is the wait()-before-restore contract, not corruption, so those
        skip quietly instead of crying wolf into the verify-failure
        counter."""
        candidates: List[int] = []
        rejected: List[int] = []
        for step in sorted(steps, reverse=True):
            if load_manifest(self._step_dir(step)) is not None:
                candidates.append(step)
            elif step in self._pending_manifests:
                logger.info(
                    "generation %d manifest still pending (async save "
                    "not drained) — not restore-eligible yet", step,
                )
            else:
                self._note_verify_failure(
                    step, "missing or invalid manifest", None
                )
                rejected.append(step)
        return candidates, rejected

    def _delete_generation(self, step: int) -> None:
        """Evict a generation that failed verification.  Leaving the
        corrupt dir in place would WEDGE its step: orbax's ``should_save``
        skips any step <= ``latest_step()``, so after a fallback the
        resumed run's re-save of this very step would silently no-op and
        the recovered progress would never persist — every restart would
        fall back again and re-lose the same work.  (The failure triplet
        already captured the forensics before this runs.)"""
        try:
            self._mgr.delete(step)
            logger.warning(
                "evicted unverifiable generation %d (a corrupt dir left "
                "in place would block its step from ever being re-saved)",
                step,
            )
        except Exception as exc:  # noqa: BLE001 — eviction is best-effort
            logger.warning(
                "could not evict unverifiable generation %d: %s", step, exc
            )

    def _restore_walk(self, steps: List[int], verify: bool):
        """The shared candidate-selection policy of :meth:`restore` and
        :meth:`restore_params`: legacy (pre-manifest) dirs restore the
        newest step unverified; durable dirs walk verified candidates
        newest-first.  Returns ``(candidates, verify, rejected)`` —
        ``rejected`` are manifest-less (torn) generations the caller may
        evict."""
        if self._is_legacy_dir(steps):
            return steps[:1], False, []
        candidates, rejected = self._verified_candidates(steps)
        return candidates, verify, rejected

    def _corruption_error(
        self, steps: List[int]
    ) -> CheckpointCorruptionError:
        return CheckpointCorruptionError(
            f"no generation under {self.directory} verifies "
            f"(steps seen: {steps}) — the store is corrupt beyond the "
            "fallback window; restore from a replica or start fresh"
        )

    def restore(
        self, state_template, *, verify: bool = True,
        evict_failed: bool = True,
    ):
        """Restore the newest VERIFIED checkpoint INTO the template's
        shardings.

        Returns (state, step); (template, None) when nothing to restore.
        A candidate generation that fails to read or fails manifest
        verification is skipped (obs event + flight-recorder dump) and the
        walk falls back to the next older one — a corrupt latest costs one
        generation of progress.  With ``evict_failed`` (the default — this
        is the TRAINER's resume verb, and the trainer owns the store) a
        failed generation is also DELETED: left in place it would wedge
        its step forever, because orbax silently skips re-saving any step
        <= the latest existing one, so the resumed run's recovered
        progress would never persist.  Raises
        :class:`CheckpointCorruptionError` when manifested generations
        exist but none verifies (restart-looping into the same corrupt
        store helps nobody).  Legacy pre-manifest directories restore the
        newest step unverified, exactly as before.
        """
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            return state_template, None
        abstract_items = jax.tree_util.tree_map(
            ocp.utils.to_shape_dtype_struct,
            self._state_items(state_template),
        )
        candidates, verify, rejected = self._restore_walk(steps, verify)
        if evict_failed:
            for step in rejected:  # torn generations: same wedge hazard
                self._delete_generation(step)
        for step in candidates:
            try:
                items = self._restore_items(step, abstract_items)
            except Exception as exc:  # noqa: BLE001 — torn data reads raise
                self._note_verify_failure(
                    step, f"restore failed: {type(exc).__name__}: {exc}",
                    None,
                )
                if evict_failed:
                    self._delete_generation(step)
                continue
            if verify and not self._verify_items(step, items):
                if evict_failed:
                    self._delete_generation(step)
                continue
            state = state_template.replace(
                step=items["state"]["step"],
                params=items["params"],
                opt_state=items["state"]["opt_state"],
                batch_stats=items["state"]["batch_stats"],
            )
            logger.info(
                "restored checkpoint step %d from %s%s",
                step, self.directory,
                "" if step == steps[0] else
                f" (fell back past {steps.index(step)} newer generation(s))",
            )
            return state, step
        raise self._corruption_error(steps)

    def restore_params(
        self,
        *,
        quantize_weights: Optional[str] = None,
        verify: bool = True,
    ):
        """Restore only the newest verified generation's ``params``.

        The serving path (``ddlt serve``) needs the weights but neither
        the optimizer state nor a TrainState template.  Post-PR
        generations store params as their own orbax item, so exactly the
        params bytes are read (an AdamW ``state`` item is ~2x the params
        — the old single-item layout forced reading all of it); legacy
        generations keep working through the full read.  Arrays come back
        host-resident (no target shardings); the engine places them onto
        its own mesh.

        ``quantize_weights="int8"`` materializes the quantized serving
        pytree directly from the f32 checkpoint (verification runs on the
        f32 arrays FIRST — quantization of corrupt weights would just
        launder the corruption into plausible-looking scales).

        Returns ``(params, step)``; ``(None, None)`` when no checkpoint.
        Fallback/corruption semantics match :meth:`restore`, minus the
        eviction: serving is a read-only consumer of a store some
        trainer owns.
        """
        if quantize_weights not in (None, "int8"):
            # validate BEFORE the restore: reading the params bytes just
            # to raise on a typo'd mode would waste the startup cost this
            # method exists to bound
            raise ValueError(
                f"unsupported quantize_weights {quantize_weights!r} "
                "(only 'int8')"
            )
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            return None, None
        # read-only consumers (serve startup) never mutate the store —
        # eviction of failed generations is the owning trainer's call
        candidates, verify, _rejected = self._restore_walk(steps, verify)
        for step in candidates:
            try:
                if self._is_composite(step):
                    # params item only: the whole point of the split
                    restored = self._mgr.restore(
                        step,
                        args=ocp.args.Composite(
                            params=ocp.args.StandardRestore()
                        ),
                    )
                    items = {"params": restored["params"]}
                else:
                    # legacy: full read, params subtree kept
                    restored = self._mgr.restore(
                        step, args=ocp.args.StandardRestore()
                    )
                    items = {"params": restored["params"]}
            except Exception as exc:  # noqa: BLE001 — torn data reads raise
                self._note_verify_failure(
                    step, f"restore failed: {type(exc).__name__}: {exc}",
                    None,
                )
                continue
            if verify and not self._verify_items(step, items):
                continue
            params = items["params"]
            logger.info(
                "restored params of checkpoint step %d from %s",
                step, self.directory,
            )
            if quantize_weights is not None:
                from distributeddeeplearning_tpu.quant.calibrate import (
                    quantize_params,
                )

                params = quantize_params(params)
                logger.info("quantized restored params to int8 (absmax PTQ)")
            return params, step
        raise self._corruption_error(steps)

    def close(self) -> None:
        """Drain + commit pending manifests, then release the manager.
        Runs on every Trainer exit path (including the PreemptionError
        unwind) — a generation whose manifest never commits is a
        generation a restart cannot use."""
        try:
            self.wait()
        finally:
            self._mgr.close()
