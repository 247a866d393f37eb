"""The hot-region registry: WHERE the dispatch-pipelining invariants live.

Every entry names a function whose body (or one loop inside it) must stay
free of per-step host syncs.  The old lint located these regions by
indentation-scraping ``inspect.getsource`` and grepping a regex — fragile
to reformatting, blind to import aliasing, and happy to flag ``float(``
inside a string.  The registry + AST checker (``analysis/host_sync.py``)
replace that: each region declares

- a **locator**: a substring of the loop-header line (``None`` = the whole
  function body is the region — e.g. ``SpeculativeDecoder.step``, which IS
  the draft->verify loop);
- **landmarks**: substrings that must appear in the region's source — the
  right-region guard (a refactor that moves the loop leaves the locator
  matching some other loop) doubled as the instrumentation guard (the obs
  spans inside the hot loops are load-bearing: the timeline is built from
  them, and the sync lint alone would not notice them vanishing);
- a **sync_budget**: the number of *designed* host syncs — lines carrying
  a live ``# sync-ok: <why>`` marker.  Exact, not a floor: waiving a NEW sync
  means editing this registry, which is a reviewed change, and a marked
  line that stops syncing is a stale-marker finding (dead waivers rot the
  allowlist's story);
- ``honor_markers=False`` for the jitted step builders: inside jit a host
  sync is a bug, full stop — there is no designed-sync story to waive
  into, so markers neither waive nor count there.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class HotRegion:
    name: str
    module: str
    qualname: str
    locator: Optional[str] = None
    landmarks: Tuple[str, ...] = ()
    sync_budget: int = 0
    honor_markers: bool = True


#: The dispatch hot loops — one designed-sync budget each.
HOT_REGIONS: Tuple[HotRegion, ...] = (
    HotRegion(
        name="trainer-step-loop",
        module="distributeddeeplearning_tpu.train.loop",
        qualname="Trainer._fit_inner",
        locator="for step_i in range",
        # goodput.mark_step is load-bearing instrumentation: the ledger's
        # 100%-of-wall accounting is built from these marks, so losing
        # them is a lint finding, not a silent accounting hole
        landmarks=("self.train_step(", "trace.span(",
                   "self.goodput.mark_step("),
        # the anomaly detector's documented one-sync-per-step price:
        # loss, grad_norm and the anomalous flag read on three marked lines
        sync_budget=3,
    ),
    HotRegion(
        name="serve-decode-loop",
        module="distributeddeeplearning_tpu.serve.scheduler",
        qualname="ContinuousBatchingScheduler.run",
        locator="while pending or active",
        # the ONE designed sync is the token readback inside the engine's
        # reading half (engine.decode_fetch, which engine.decode ends in:
        # not in this region's source), so the loop body itself budgets 0:
        # with a step in flight it calls the two halves a turn apart, with
        # none it calls engine.decode
        # the spans that cover the turn outside the step are load-bearing
        # too: the benchmark's turn and idle-attribution metrics read them
        landmarks=("engine.decode_dispatch(", "engine.decode_fetch(",
                   "engine.decode(", "trace.span(", '"serve/poll"',
                   '"serve/admission"', '"serve/emit"'),
        sync_budget=0,
    ),
    HotRegion(
        name="fleet-dispatch-loop",
        module="distributeddeeplearning_tpu.serve.fleet",
        qualname="FleetRouter.serve",
        locator="while len(results) < len(flights)",
        # pure host bookkeeping by design: device values never cross the
        # process boundary, so ANY sync token here is a leak
        landmarks=("self._outbox.get", "handle_death"),
        sync_budget=0,
    ),
    HotRegion(
        name="spec-draft-verify-loop",
        module="distributeddeeplearning_tpu.spec.decode",
        qualname="SpeculativeDecoder.step",
        locator=None,  # the whole method IS the draft->verify loop
        landmarks=("drafter.propose", "self._verify_jit"),
        # the one designed readback: committed tokens + acceptance +
        # finiteness ride a single sync across three marked lines
        sync_budget=3,
    ),
    HotRegion(
        name="fleet-worker-metrics-ship",
        module="distributeddeeplearning_tpu.serve.fleet",
        qualname="_ship_metrics",
        # the shipped state is host counters + histogram buckets by
        # construction — a sync token here means engine state leaked
        # into the metrics plane
        landmarks=("outbox.put(", "get_registry().state()"),
        sync_budget=0,
    ),
    HotRegion(
        name="fleet-reload-apply",
        module="distributeddeeplearning_tpu.serve.fleet",
        qualname="_apply_reload",
        # the live-reload body runs INSIDE the serve loop (the scheduler's
        # idle barrier): host checkpoint I/O plus one device_put upload by
        # design — a device READBACK here stalls the whole fleet's reload
        # barrier on a sync it never needed.  The landmarks pin the
        # verified-restore -> in-place-swap shape (a refactor that skips
        # verification or rebuilds the engine fails lint, not review).
        landmarks=("restore_params(", "reload_params("),
        sync_budget=0,
    ),
    HotRegion(
        name="serve-preemption-decision",
        module="distributeddeeplearning_tpu.serve.scheduler",
        qualname="ContinuousBatchingScheduler._preemption_victim",
        locator=None,  # the whole method IS the decision
        # the preemption decision rides signals already on host — class
        # ranks, per-slot generated-token counts, slot ids — so ANY sync
        # token here means a device value leaked into victim selection
        # (the overload path would then stall exactly when it must not).
        # Landmarks pin the least-progress-within-lowest-class shape.
        landmarks=("st.generated", "self._class_rank"),
        sync_budget=0,
    ),
    HotRegion(
        name="kv-tier-spill",
        module="distributeddeeplearning_tpu.serve.kv_tier",
        qualname="HostPageTier.spill_in",
        # the host tier's ONE designed sync: the D2H page readback that
        # copies a cold page's leaves (k/v values AND quant scales) into
        # the pinned host pool.  Exactly one marked np.asarray — a
        # second readback here doubles the spill cost of every demotion.
        landmarks=("np.asarray(",),
        sync_budget=1,
    ),
    HotRegion(
        name="kv-tier-prefetch",
        module="distributeddeeplearning_tpu.serve.kv_tier",
        qualname="HostPageTier.dispatch_restore",
        # the restore path must stay ASYNC: jax.device_put dispatches
        # the H2D transfer and returns immediately — the landmark pins
        # that dispatch shape, and ANY sync token here would turn the
        # prefetch the admission gate overlaps with decode into a stall.
        landmarks=("jax.device_put(",),
        sync_budget=0,
    ),
    HotRegion(
        name="serve-tier-pump",
        module="distributeddeeplearning_tpu.serve.scheduler",
        qualname="ContinuousBatchingScheduler._tier_pump",
        # one pass per scheduler iteration: retire landed prefetches,
        # then demote the coldest reclaimable pages when the free-page
        # cushion or the HBM forecast says pressure is near.  The
        # designed D2H sync lives inside HostPageTier.spill_in (its own
        # region above) — THIS body reads host counters and the ledger
        # forecast only, so it budgets 0.
        landmarks=("engine.tier_inflight(", "engine.spill_cold_pages("),
        sync_budget=0,
    ),
)

#: Jitted step builders: no host-sync token at all — inside jit it would
#: either crash or silently fall back to host math; markers don't waive.
JIT_BUILDER_REGIONS: Tuple[HotRegion, ...] = (
    HotRegion(
        name="train-step-builder",
        module="distributeddeeplearning_tpu.train.step",
        qualname="build_train_step",
        honor_markers=False,
    ),
    # the flash-decode kernel dispatch: traced inside every decode/chunk/
    # verify program, so ANY host-sync token is a per-step round-trip
    # hiding inside the compiled step — zero designed syncs, markers
    # don't waive.  The landmarks double as the dispatch-shape guard:
    # both the Pallas kernel call (via the per-shard shard_map wrapper
    # ``_pallas_paged``) and the legacy gather path must remain
    # reachable from this one site.
    HotRegion(
        name="flash-decode-dispatch",
        module="distributeddeeplearning_tpu.ops.flash_decode",
        qualname="decode_attention_paged",
        landmarks=("_pallas_paged(", "_gather_decode_paged("),
        honor_markers=False,
    ),
    HotRegion(
        name="comm-overlap-step-builder",
        module="distributeddeeplearning_tpu.train.step",
        qualname="_build_comm_overlap_step",
        honor_markers=False,
    ),
    HotRegion(
        name="eval-step-builder",
        module="distributeddeeplearning_tpu.train.step",
        qualname="build_eval_step",
        honor_markers=False,
    ),
)

#: The obs hot API lives INSIDE both hot loops (spans around every step),
#: so it gets the same treatment; its two documented host-scalar
#: coercions are marked and budgeted.
_OBS_TRACE = "distributeddeeplearning_tpu.obs.trace"
_OBS_REG = "distributeddeeplearning_tpu.obs.registry"
_OBS_RECORDER = "distributeddeeplearning_tpu.obs.recorder"
_OBS_GOODPUT = "distributeddeeplearning_tpu.obs.goodput"
_OBS_ATTRIB = "distributeddeeplearning_tpu.obs.attrib"
OBS_HOT_REGIONS: Tuple[HotRegion, ...] = (
    HotRegion(name="obs-tracer-span", module=_OBS_TRACE, qualname="Tracer.span"),
    HotRegion(name="obs-tracer-event", module=_OBS_TRACE, qualname="Tracer.event"),
    HotRegion(name="obs-span-enter", module=_OBS_TRACE, qualname="_Span.__enter__"),
    HotRegion(name="obs-span-exit", module=_OBS_TRACE, qualname="_Span.__exit__"),
    HotRegion(
        name="obs-nullspan-enter", module=_OBS_TRACE, qualname="_NullSpan.__enter__"
    ),
    HotRegion(
        name="obs-nullspan-exit", module=_OBS_TRACE, qualname="_NullSpan.__exit__"
    ),
    # runs at every collection, in whatever the process was doing
    HotRegion(name="obs-gc-hook", module=_OBS_TRACE, qualname="_on_gc"),
    HotRegion(
        name="obs-histogram-record",
        module=_OBS_REG,
        qualname="Histogram.record",
        sync_budget=1,  # the documented host-scalar coercion
    ),
    HotRegion(name="obs-counter-inc", module=_OBS_REG, qualname="Counter.inc"),
    HotRegion(
        name="obs-gauge-set",
        module=_OBS_REG,
        qualname="Gauge.set",
        sync_budget=1,  # the documented host-scalar coercion
    ),
    # the flight-recorder record path: ON even with the tracer disabled,
    # so it sits inside every hot loop unconditionally — zero designed
    # syncs (entries are host timestamps/scalars by contract) and the
    # ring append is the whole cost
    HotRegion(
        name="obs-recorder-record",
        module=_OBS_RECORDER,
        qualname="FlightRecorder.record",
        landmarks=("self._ring.append",),
    ),
    HotRegion(
        name="obs-recorder-span-enter",
        module=_OBS_RECORDER,
        qualname="_RecorderSpan.__enter__",
    ),
    HotRegion(
        name="obs-recorder-span-exit",
        module=_OBS_RECORDER,
        qualname="_RecorderSpan.__exit__",
        landmarks=("self._rec.record",),
    ),
    # the goodput ledger's record path: called at EVERY phase boundary
    # of the trainer hot loop — one perf_counter read + dict math on
    # host floats, ZERO designed syncs (a category recorded via a
    # host-coercing float(...) of a device value is exactly the seeded
    # lint_violations fixture bug; markers would not waive a new sync
    # into this budget without editing this registry)
    HotRegion(
        name="obs-goodput-mark",
        module=_OBS_GOODPUT,
        qualname="GoodputLedger.mark",
        landmarks=("time.perf_counter()",),
    ),
    HotRegion(
        name="obs-goodput-mark-step",
        module=_OBS_GOODPUT,
        qualname="GoodputLedger.mark_step",
        landmarks=("self.mark(",),
    ),
    # the program-cost tracker's call path wraps EVERY jitted entry
    # point (train step, decode, verify, ...): steady state is two jit
    # cache-size reads around the forwarded call, and even the first-
    # compile record touches only aval metadata — ZERO designed syncs
    # (a buffer read here would serialize every step it wraps).  The
    # landmark pins the forwarded dispatch: the wrapper must stay a
    # pass-through, never grow its own device logic.
    HotRegion(
        name="obs-attrib-record",
        module=_OBS_ATTRIB,
        qualname="TrackedProgram.__call__",
        landmarks=("fn(*args, **kwargs)",),
    ),
)

ALL_REGIONS: Tuple[HotRegion, ...] = (
    HOT_REGIONS + JIT_BUILDER_REGIONS + OBS_HOT_REGIONS
)


def get_region(name: str) -> HotRegion:
    for region in ALL_REGIONS:
        if region.name == name:
            return region
    raise KeyError(f"unknown hot region {name!r}")
