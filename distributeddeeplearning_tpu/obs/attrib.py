"""Per-program cost attribution: what every compiled program costs, measured
at the source.

The obs stack can say where a *second* goes (goodput ledger) and how a
*metric* moved across revisions (``ddlt obs history``), but not where a
FLOP or a byte of HBM goes: which compiled program is compute-bound,
which is bandwidth-bound, which host straggles.  This module is that
attribution layer:

- **Program cost registry** (:class:`ProgramCostRegistry` +
  :func:`tracked_jit`): every jitted entry point — the train step, the
  serve engines' prefill/insert/chunk/decode/scrub, the speculative
  verify/rollback — is wrapped so that at FIRST COMPILE (detected via the
  jit cache growing, so steady-state calls pay two C++ attribute reads
  and nothing else) the call's aval signature is recorded.  On demand,
  :meth:`~ProgramCostRegistry.collect` re-lowers each recorded signature
  and reads XLA's own cost model — ``Lowered.cost_analysis()`` flops /
  bytes-accessed WITHOUT a second backend compile, and (opt-in, one AOT
  compile per program) ``Compiled.memory_analysis()`` temp/argument/
  output/alias bytes.  Backend-portable: the whole path works on the CPU
  test mesh, which is what makes the attribution artifact a tier-1
  citizen.
- **Straggler / step-phase timing** (:func:`straggler_report`): per-host
  step-phase durations extracted from exported tracer shards (the same
  Chrome-trace shards the fleet merge aligns), naming the slowest host
  per phase and the skew.  Durations are measured per-host on ONE
  monotonic clock each, so wall-clock offset between hosts can neither
  reorder a host's own spans nor produce a negative duration — the merge
  only shifts timestamps (pinned in ``tests/test_attrib.py``).
- **Compute-vs-collective split** (:func:`compute_collective_split`): an
  analytic estimate from counted flops and bytes-on-wire against the
  chip's peaks — labeled ``estimated``, never passed off as a
  measurement.
- **Reporting** (:func:`build_report` / :func:`self_check`): program
  costs + the live HBM ledger (:mod:`.ledger`) + achieved-vs-roofline
  per program (``utils/roofline.program_roofline``) in one JSON frame —
  the body of ``ddlt obs attrib`` and the ``ATTRIB_r{NN}.json`` bench
  artifact, whose tracked metrics register in ``ddlt obs history``.

The registry holds programs through WEAK references: a garbage-collected
engine's programs drop out instead of the registry pinning every
compiled executable (and its params) for the life of the process.  The
record path is a registered hot region (``obs-attrib-record`` in
``analysis/regions.py``): zero designed syncs — shapes and dtypes are
aval metadata, never buffer reads.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import weakref
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from distributeddeeplearning_tpu.obs import recorder as _recorder_mod

__all__ = [
    "ProgramCost",
    "TrackedProgram",
    "ProgramCostRegistry",
    "tracked_jit",
    "get_programs",
    "set_programs",
    "step_phase_stats",
    "straggler_report",
    "compute_collective_split",
    "build_report",
    "self_check",
    "PHASE_SPANS",
]

#: signatures retained per program (prefill buckets are the widest real
#: family: log2(max_seq) of them; 16 bounds a pathological caller)
MAX_SIGNATURES = 16

#: the step-phase span names straggler attribution aggregates — the spans
#: the trainer/scheduler hot loops already emit
PHASE_SPANS = (
    "train/data_wait",
    "train/step",
    "train/checkpoint",
    "serve/decode_step",
    "serve/spec_step",
    "serve/prefill_chunk",
)


def _abstract(leaf: Any) -> Any:
    """Array-ish leaves -> ShapeDtypeStruct (metadata only — no buffer
    touch, safe even on a just-donated argument); everything else
    (static flags, python scalars) passes through verbatim."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return leaf
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _sig_key(args: Tuple, kwargs: Dict) -> str:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(map(str, shape))}]")
        else:
            parts.append(repr(leaf))
    return f"{treedef}|{';'.join(parts)}"


@dataclasses.dataclass
class ProgramCost:
    """XLA's cost model for one (program, signature): model flops and
    bytes accessed from ``cost_analysis()`` (pre-optimization — the MFU-
    numerator convention), plus ``memory_analysis()`` HBM residency when
    a compile was paid for it."""

    name: str
    signature: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    available: bool = False
    error: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class TrackedProgram:
    """A jitted callable plus its compile-time signature log.

    Transparent to callers: ``__call__`` forwards, every other attribute
    (``lower`` / ``trace`` / ``_cache_size`` — the program audit and the
    lint pins use them) resolves on the wrapped jit.  A new compile is
    detected by the jit cache growing across the call; only then is the
    signature abstracted and recorded — the steady-state overhead is two
    cache-size reads per call, no tree walk, no sync.
    """

    __slots__ = ("name", "_fn", "_sigs", "_costs", "__weakref__")

    def __init__(self, name: str, fn):
        self.name = name
        self._fn = fn
        # key -> (abstract args, abstract kwargs); insertion-ordered
        self._sigs: Dict[str, Tuple[Tuple, Dict]] = {}
        self._costs: Dict[str, ProgramCost] = {}

    # -- the hot path (registered region obs-attrib-record) ---------------
    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            before = fn._cache_size()
        except Exception:
            before = None
        out = fn(*args, **kwargs)
        if before is None:
            # duck-typed callee without a jit cache: record once
            if not self._sigs:
                self._record(args, kwargs)
            return out
        try:
            grew = fn._cache_size() != before
        except Exception:  # pragma: no cover - cache_size raced away
            grew = False
        if grew:
            # first compile of this shape: abstract the signature (aval
            # metadata only — donated buffers are already gone, their
            # shapes are not)
            self._record(args, kwargs)
        return out

    def _record(self, args: Tuple, kwargs: Dict) -> None:
        if len(self._sigs) >= MAX_SIGNATURES:
            return
        import jax

        key = _sig_key(args, kwargs)
        if key in self._sigs:
            return
        self._sigs[key] = (
            jax.tree_util.tree_map(_abstract, args),
            jax.tree_util.tree_map(_abstract, kwargs),
        )

    def __getattr__(self, item):
        return getattr(object.__getattribute__(self, "_fn"), item)

    # -- collection --------------------------------------------------------
    @property
    def signatures(self) -> List[str]:
        return list(self._sigs)

    def collect(self, *, memory: bool = False) -> List[ProgramCost]:
        """Resolve every recorded signature to a :class:`ProgramCost`.

        ``cost_analysis`` comes off the re-lowered program (tracing cost
        only — no second backend compile); ``memory=True`` additionally
        AOT-compiles each signature once for ``memory_analysis()``
        temp/arg/output bytes (cached: later collects are free).  A
        signature that fails to lower records its error instead of
        raising — attribution must never take down the run it measures.
        """
        out: List[ProgramCost] = []
        for key, (args, kwargs) in list(self._sigs.items()):
            cached = self._costs.get(key)
            if cached is not None and (
                not memory or cached.temp_bytes is not None
                or cached.error is not None
            ):
                out.append(cached)
                continue
            cost = ProgramCost(name=self.name, signature=key)
            try:
                lowered = self._fn.lower(*args, **kwargs)
                ca = lowered.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                ca = ca or {}
                # a pure data-movement program (scrub, rollback) may
                # carry no "flops" entry at all — that is a zero-FLOP
                # program with a perfectly good byte count, not a
                # failed analysis
                cost.flops = float(ca.get("flops", 0.0) or 0.0)
                nbytes = ca.get("bytes accessed", ca.get("bytes_accessed"))
                cost.bytes_accessed = (
                    float(nbytes) if nbytes is not None else 0.0
                )
                cost.available = True
                if memory:
                    ma = lowered.compile().memory_analysis()
                    cost.argument_bytes = int(ma.argument_size_in_bytes)
                    cost.output_bytes = int(ma.output_size_in_bytes)
                    cost.temp_bytes = int(ma.temp_size_in_bytes)
                    cost.alias_bytes = int(ma.alias_size_in_bytes)
                    cost.generated_code_bytes = int(
                        ma.generated_code_size_in_bytes
                    )
            except Exception as exc:  # noqa: BLE001 — recorded, not raised
                cost.error = f"{type(exc).__name__}: {exc}"
            self._costs[key] = cost
            out.append(cost)
        return out


class ProgramCostRegistry:
    """Every tracked program in the process, weakly held.

    ``collect`` resolves costs; the most recent table is cached so the
    flight recorder's crash dumps can attach it WITHOUT lowering anything
    mid-failure."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: List["weakref.ref[TrackedProgram]"] = []
        self.last_table: List[Dict[str, Any]] = []

    def track(self, name: str, fn) -> TrackedProgram:
        prog = TrackedProgram(name, fn)
        with self._lock:
            self._programs = [r for r in self._programs if r() is not None]
            self._programs.append(weakref.ref(prog))
        return prog

    def programs(self) -> List[TrackedProgram]:
        with self._lock:
            live = [r() for r in self._programs]
            return [p for p in live if p is not None]

    def names(self) -> List[str]:
        return sorted({p.name for p in self.programs()})

    def collect(
        self, *, memory: bool = False, registry=None,
    ) -> Dict[str, List[ProgramCost]]:
        """Costs for every live program, grouped by name.  With a
        metrics ``registry`` the representative (largest-flops)
        signature per name is published as ``attrib.<name>.flops`` /
        ``attrib.<name>.bytes_accessed`` gauges — the wire form the
        fleet metric ship and snapshot rows already carry."""
        grouped: Dict[str, List[ProgramCost]] = {}
        for prog in self.programs():
            costs = prog.collect(memory=memory)
            if not costs:
                continue  # tracked but never compiled (e.g. scrub on a
                # healthy run) — nothing to attribute, nothing to gate
            grouped.setdefault(prog.name, []).extend(costs)
        self.last_table = [
            c.to_dict() for costs in grouped.values() for c in costs
        ]
        if registry is not None:
            for name, costs in grouped.items():
                best = max(
                    (c for c in costs if c.flops is not None),
                    key=lambda c: c.flops, default=None,
                )
                if best is None:
                    continue
                registry.gauge(f"attrib.{name}.flops").set(best.flops)
                if best.bytes_accessed is not None:
                    registry.gauge(f"attrib.{name}.bytes_accessed").set(
                        best.bytes_accessed
                    )
        return grouped

    def dump_table(self) -> List[Dict[str, Any]]:
        """The crash-dump attachment: the cached cost table when a
        collect has run, otherwise the bare signature inventory —
        NEVER a fresh lowering (this runs mid-failure)."""
        if self.last_table:
            return self.last_table
        return [
            {"name": p.name, "signature": s, "available": False}
            for p in self.programs()
            for s in p.signatures
        ]


# -- process-global program registry ----------------------------------------

_PROGRAMS = ProgramCostRegistry()


def get_programs() -> ProgramCostRegistry:
    return _PROGRAMS


def set_programs(registry: ProgramCostRegistry) -> ProgramCostRegistry:
    global _PROGRAMS
    _PROGRAMS = registry
    return registry


def tracked_jit(name: str, fn) -> TrackedProgram:
    """Wrap a jitted callable into the process cost registry — the one-
    line instrumentation every jitted entry point goes through."""
    return _PROGRAMS.track(name, fn)


#: the custom-call target a Pallas TPU kernel lowers to (Mosaic)
MOSAIC_CALL_TARGET = "tpu_custom_call"


def mosaic_call_counts(programs: Sequence[TrackedProgram]) -> Dict[str, int]:
    """``program name -> Mosaic custom calls`` in the lowered text of each
    program's compiled signatures (the fewest over its signatures; 0 for
    a program that never compiled).  The evidence that a Pallas kernel is
    IN the program a run executed — read off the program itself, not off
    the option that asked for it; a ``lax.scan`` body counts once.
    Re-traces each signature, so call it once at the end of a run."""
    out: Dict[str, int] = {}
    for prog in programs:
        counts = [
            prog.lower(*args, **kwargs).as_text().count(MOSAIC_CALL_TARGET)
            for args, kwargs in list(prog._sigs.values())
        ]
        out[prog.name] = min(counts, default=0)
    return out


# the program-cost table rides every flight-recorder dump (cached table
# only — no lowering mid-crash); see obs/recorder.register_dump_context
_recorder_mod.register_dump_context(
    "program_costs", lambda: get_programs().dump_table()
)


# -- straggler / step-phase timing ------------------------------------------

def _iter_shards(shards: Iterable[Any]):
    for shard in shards:
        if isinstance(shard, str):
            with open(shard) as f:
                yield json.load(f)
        else:
            yield shard


def step_phase_stats(
    events: Sequence[Dict[str, Any]],
    phases: Sequence[str] = PHASE_SPANS,
) -> Dict[str, Dict[Any, Dict[str, float]]]:
    """Per-(phase, pid) duration stats over one Chrome-trace event list.

    Durations come from each span's own ``dur`` field — a per-host
    monotonic measurement that no cross-host clock offset can touch —
    so skewed shards yield the same stats as aligned ones."""
    wanted = set(phases)
    acc: Dict[str, Dict[Any, Dict[str, float]]] = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("name") not in wanted:
            continue
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        row = acc.setdefault(ev["name"], {}).setdefault(
            ev.get("pid", 0),
            {"count": 0, "total_ms": 0.0, "max_ms": 0.0},
        )
        row["count"] += 1
        row["total_ms"] += dur_ms
        if dur_ms > row["max_ms"]:
            row["max_ms"] = dur_ms
    for per_pid in acc.values():
        for row in per_pid.values():
            row["mean_ms"] = round(row["total_ms"] / row["count"], 4)
            row["total_ms"] = round(row["total_ms"], 4)
            row["max_ms"] = round(row["max_ms"], 4)
    return acc


def straggler_report(
    shards: Iterable[Any],
    phases: Sequence[str] = PHASE_SPANS,
) -> Dict[str, Any]:
    """Slowest-host attribution over per-host tracer shards.

    ``shards``: Chrome-trace dicts or file paths (the per-process
    exports ``Tracer.export`` writes and ``obs.fleet`` merges).  Hosts
    are named by their shard's ``process_name`` metadata (pid fallback).
    Per phase: per-host mean/total/max span durations, the slowest and
    fastest host by mean, and ``skew_pct`` — how much longer the
    straggler runs the phase than the fastest host.  ``negative_spans``
    counts spans with negative duration and must be 0: durations are
    single-clock measurements, which is exactly why wall-clock offset
    between hosts cannot corrupt this table (pinned under synthetic
    skew in the tests)."""
    merged_events: List[Dict[str, Any]] = []
    host_names: Dict[Any, str] = {}
    negative = 0
    # pids are only unique WITHIN a shard (two containerized workers on
    # different machines can both be pid 1 — the same collision
    # obs.fleet.merge_fleet_trace remaps), so each shard gets its own
    # pid namespace: first shard to use a pid keeps it, later shards
    # colliding on it are suffixed so two hosts never merge into one row
    pid_owner: Dict[Any, int] = {}
    for idx, shard in enumerate(_iter_shards(shards)):
        events = shard.get("traceEvents") if isinstance(shard, dict) else shard
        local: Dict[Any, Any] = {}

        def qualify(pid: Any) -> Any:
            if pid not in local:
                if pid_owner.setdefault(pid, idx) == idx:
                    local[pid] = pid
                else:
                    local[pid] = f"{pid}#{idx}"
            return local[pid]

        for ev in events or []:
            pid = qualify(ev.get("pid", 0))
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                name = (ev.get("args") or {}).get("name")
                if name:
                    host_names[pid] = str(name)
            elif ev.get("ph") == "X":
                if float(ev.get("dur", 0.0)) < 0.0:
                    negative += 1
                merged_events.append(
                    ev if ev.get("pid", 0) == pid else {**ev, "pid": pid}
                )
    stats = step_phase_stats(merged_events, phases)
    report: Dict[str, Any] = {
        "hosts": sorted(
            {host_names.get(pid, str(pid))
             for per in stats.values() for pid in per}
        ),
        "negative_spans": negative,
        "phases": {},
    }
    for phase, per_pid in sorted(stats.items()):
        rows = {
            host_names.get(pid, str(pid)): row
            for pid, row in per_pid.items()
        }
        slowest = max(rows, key=lambda h: rows[h]["mean_ms"])
        fastest = min(rows, key=lambda h: rows[h]["mean_ms"])
        fast_mean = rows[fastest]["mean_ms"]
        report["phases"][phase] = {
            "per_host": rows,
            "slowest_host": slowest,
            "fastest_host": fastest,
            "skew_pct": round(
                (rows[slowest]["mean_ms"] - fast_mean)
                / fast_mean * 100.0, 2,
            ) if fast_mean > 0 else 0.0,
        }
    return report


def compute_collective_split(
    flops: float,
    wire_bytes: float,
    *,
    peak_flops: float,
    interconnect_gbps: float,
    measured_step_s: Optional[float] = None,
) -> Dict[str, Any]:
    """Analytic compute-vs-collective step decomposition.

    ``compute_s = flops / peak_flops``; ``collective_s = wire_bytes /
    interconnect``.  This is a MODEL (perfect overlap would hide the
    smaller term entirely; zero overlap serializes them) — the block is
    stamped ``estimated: True`` and, given a measured step time, reports
    how much wall the two ideals leave unexplained."""
    compute_s = flops / peak_flops if peak_flops > 0 else 0.0
    collective_s = (
        wire_bytes / (interconnect_gbps * 1e9)
        if interconnect_gbps > 0 else 0.0
    )
    total = compute_s + collective_s
    out: Dict[str, Any] = {
        "estimated": True,
        "compute_s": round(compute_s, 6),
        "collective_s": round(collective_s, 6),
        "compute_fraction": round(compute_s / total, 4) if total else 0.0,
        "collective_fraction": (
            round(collective_s / total, 4) if total else 0.0
        ),
        "bound": (
            "compute" if compute_s >= collective_s else "collective"
        ),
    }
    if measured_step_s is not None and measured_step_s > 0:
        out["measured_step_s"] = round(measured_step_s, 6)
        out["unexplained_s"] = round(
            max(0.0, measured_step_s - max(compute_s, collective_s)), 6
        )
    return out


# -- report choreography -----------------------------------------------------

def reference_peaks() -> Tuple[float, float, str]:
    """(peak_tflops, peak_hbm_gbps, source) for the roofline columns:
    the real chip's datasheet peaks (source ``"device"``) when
    :func:`utils.hardware` knows BOTH its compute and HBM-bandwidth
    ceilings.  On the CPU backend — the hermetic self-check — the v5e
    nominals come back LABELED ``"v5e-nominal-reference"``, so the ratios
    read "vs a v5e", never as this host's ceiling.  An accelerator that
    is missing from the peaks tables raises: a roofline against another
    chip's ceiling is a wrong number, not a default."""
    import jax

    from distributeddeeplearning_tpu.utils.hardware import (
        peak_bf16_flops,
        peak_hbm_gbps,
    )

    device = jax.devices()[0]
    peak = peak_bf16_flops(device)
    bw = peak_hbm_gbps(device)
    if peak is not None and bw is not None:
        return peak / 1e12, bw, "device"
    if device.platform != "cpu":
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            "in utils/hardware.py (_PEAK_BF16_FLOPS / _PEAK_HBM_GBPS) — "
            "add the chip's datasheet figures there"
        )
    return 197.0, 819.0, "v5e-nominal-reference"


def _time_decode(engine, steps: int = 5):
    """Steady-state decode wall (min over ``steps`` single dispatches —
    min is the noise-robust estimate on a shared host).  Assumes the
    engine already compiled its decode program (a scheduler run just
    drove it)."""
    import time

    import numpy as np

    tokens = np.ones(engine.batch_slots, np.int32)
    pos = np.full(engine.batch_slots, 1, np.int32)
    walls = []
    for _ in range(steps):
        t0 = time.perf_counter()
        engine.decode(tokens, pos)
        walls.append(time.perf_counter() - t0)
    return min(walls)

def build_report(
    *,
    programs: Optional[ProgramCostRegistry] = None,
    ledger=None,
    measured_step_s: Optional[Dict[str, float]] = None,
    memory: bool = True,
    peak_tflops: Optional[float] = None,
    peak_hbm_gbps: Optional[float] = None,
    match_tolerance_pct: float = 1.0,
) -> Dict[str, Any]:
    """The attribution frame ``ddlt obs attrib`` prints and the ATTRIB
    artifact embeds: per-program cost rows (+ achieved-vs-roofline for
    programs with a measured step time), the HBM-ledger snapshot with
    its live-bytes reconciliation, and the gate verdicts."""
    from distributeddeeplearning_tpu.obs.ledger import get_ledger
    from distributeddeeplearning_tpu.obs.registry import get_registry
    from distributeddeeplearning_tpu.utils.roofline import program_roofline

    programs = programs if programs is not None else get_programs()
    ledger = ledger if ledger is not None else get_ledger()
    measured_step_s = measured_step_s or {}

    grouped = programs.collect(memory=memory, registry=get_registry())
    prog_block: Dict[str, Any] = {}
    for name, costs in sorted(grouped.items()):
        best = max(
            (c for c in costs if c.flops is not None),
            key=lambda c: c.flops, default=None,
        )
        row: Dict[str, Any] = {
            "signatures": len(costs),
            "flops": best.flops if best else None,
            "bytes_accessed": best.bytes_accessed if best else None,
            "argument_bytes": best.argument_bytes if best else None,
            "output_bytes": best.output_bytes if best else None,
            "temp_bytes": best.temp_bytes if best else None,
            "alias_bytes": best.alias_bytes if best else None,
            "available": best is not None,
            "errors": [c.error for c in costs if c.error],
        }
        step_s = measured_step_s.get(name)
        if (
            best is not None and step_s
            and best.flops is not None and best.bytes_accessed is not None
        ):
            row["roofline"] = program_roofline(
                best.flops, best.bytes_accessed, step_s,
                peak_tflops=peak_tflops, peak_hbm_gbps=peak_hbm_gbps,
            )
        prog_block[name] = row

    ledger_block = ledger.snapshot(reconcile=True)
    live = ledger_block.get("live_bytes", 0)
    accounted = ledger_block.get("total_bytes", 0)
    match_pct = (
        abs(live - accounted) / live * 100.0 if live else 0.0
    )
    gates = {
        "programs_covered": bool(prog_block) and all(
            row["available"] for row in prog_block.values()
        ),
        "owner_totals_match_live": match_pct <= match_tolerance_pct,
        "residual_under_limit": bool(
            ledger_block.get("residual_under_limit", False)
        ),
    }
    return {
        "programs": prog_block,
        "programs_covered": sum(
            1 for row in prog_block.values() if row["available"]
        ),
        "ledger": ledger_block,
        "owner_match_pct": round(match_pct, 4),
        "owner_match_tolerance_pct": match_tolerance_pct,
        "unaccounted_hbm_pct": ledger_block.get("unaccounted_pct", 0.0),
        "gates": gates,
    }


def self_check(*, spec: bool = True) -> Tuple[bool, Dict[str, Any]]:
    """The hermetic ``ddlt obs attrib --check`` body: build tiny dense +
    paged engines (and a speculative decoder) on the current backend,
    serve a few synthetic requests through the real scheduler, then
    verify the attribution layer's own gates — every tracked program
    resolves a cost, the ledger's owner totals reconcile against the
    process's live device bytes within the match tolerance, and the
    unaccounted-HBM residual stays under its limit.

    Runs in seconds on the CPU backend (tiny dims) — the ``make
    obs-gate`` half that needs jax.  Returns ``(ok, report)``."""
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        init_params,
    )
    from distributeddeeplearning_tpu.serve.engine import (
        InferenceEngine,
        PagedInferenceEngine,
    )
    from distributeddeeplearning_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
        synthetic_requests,
    )

    dims = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64,
                vocab_size=211)
    max_seq = 48
    params = init_params(jax.random.key(0), max_len=max_seq, **dims)
    dense = InferenceEngine(
        params, num_heads=dims["num_heads"], batch_slots=2,
        max_seq=max_seq,
    )
    paged = PagedInferenceEngine(
        params, num_heads=dims["num_heads"], batch_slots=2,
        max_seq=max_seq, page_size=8, prefill_chunk=8,
    )
    reqs = synthetic_requests(
        4, vocab_size=dims["vocab_size"], max_prompt=12,
        rng=np.random.default_rng(0),
    )
    ContinuousBatchingScheduler(dense, max_new_tokens=4).run(list(reqs))
    ContinuousBatchingScheduler(paged, max_new_tokens=4).run(list(reqs))
    measured = {
        f"serve.dense.{dense.kv_dtype}.decode": _time_decode(dense),
        f"serve.paged.{paged.kv_dtype}.decode": _time_decode(paged),
    }
    if spec:
        from distributeddeeplearning_tpu.spec.decode import (
            SpeculativeDecoder,
        )

        decoder = SpeculativeDecoder(
            paged, drafter="truncated", draft_tokens=2, draft_layers=1,
        )
        ContinuousBatchingScheduler(
            paged, max_new_tokens=4, spec_decoder=decoder,
        ).run(list(reqs))
    peak_tflops, peak_gbps, peaks_source = reference_peaks()
    report = build_report(
        memory=True, measured_step_s=measured,
        peak_tflops=peak_tflops, peak_hbm_gbps=peak_gbps,
    )
    report["peaks_source"] = peaks_source
    expected = {
        "serve.dense.float32.prefill",
        "serve.dense.float32.decode",
        "serve.paged.float32.prefill_chunk",
        "serve.paged.float32.decode",
    }
    if spec:
        expected.add("spec.paged.verify")
    missing = sorted(expected - set(report["programs"]))
    report["expected_programs_missing"] = missing
    report["gates"]["expected_programs_present"] = not missing
    ok = all(report["gates"].values())
    return ok, report
