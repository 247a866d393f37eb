"""Span-based host tracer: one timeline for train, serve and resilience.

Five subsystems each grew their own stats channel (ServeReport counters,
the COMMS overlap twin, RESILIENCE recovery accounting, roofline tables,
watchdog stack dumps) with no way to put a train step's data wait, a serve
request's prefill chunks and a preemption event on ONE clock.  This module
is that clock: nested host-side spans plus instant events, exported as
Chrome-trace JSON (``chrome://tracing`` / Perfetto open it directly), with
``jax.profiler.TraceAnnotation`` pass-through so the same span names land
inside the device profile and :mod:`.profile` can merge the two timelines.

Design constraints (enforced by the ``analysis/`` host-sync checker via
``tests/test_hotloop_lint.py``):

- **zero-sync**: nothing in the span path reads a device value — spans
  time host wall-clock only, so instrumenting a hot loop can never
  serialize dispatch;
- **near-zero cost when disabled**: ``span()`` on a disabled tracer
  returns a shared no-op context manager without reading the clock or
  allocating an event — the hot paths stay hot with observability off
  (the default);
- **follows any profiler capture**: while a ``jax.profiler`` capture is
  live (``TraceAnnotation.is_enabled()``, one ~60 ns call per span) the
  tracer records exactly as if enabled — whoever started the capture (a
  benchmark harness, an operator attaching to a live ``ddlt serve``)
  gets the program's spans in the capture's host plane, on the
  profiler's clock, with no switch in the program;
- **bounded**: the event list is a ring of ``max_events`` entries, the
  oldest dropped and counted (``dropped``), so a tracer left enabled on
  a days-long worker cannot grow without bound.

Usage::

    tracer = get_tracer()                    # process-global, disabled
    tracer.enable()                          # or configure(enabled=True)
    with tracer.span("train/step", step=12):
        ...
    tracer.event("preempted", step=12)       # instant event
    tracer.export("trace.json")              # Chrome trace JSON
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from distributeddeeplearning_tpu.obs import recorder as _recorder_mod
from distributeddeeplearning_tpu.obs.recorder import (
    FlightRecorder,
    _RecorderSpan,
)

__all__ = [
    "Tracer",
    "PROCESS_RECORDER",
    "get_tracer",
    "set_tracer",
    "configure",
]

#: sentinel recorder binding: "whatever the PROCESS recorder currently
#: is", resolved at record time — so ``set_recorder`` swaps (tests,
#: resets) take effect on the global tracer immediately instead of
#: leaving it bound to the recorder that existed at import
PROCESS_RECORDER: Any = object()

#: how many events a tracer keeps (oldest dropped, counted in ``dropped``):
#: at the serve loop's ~12 spans a turn, and a ``host/gc`` a collection,
#: a minute or two of a busy loop at 9 ms a turn; well under 100 MB of
#: event dicts at worst
MAX_EVENTS = 131_072


class _NullSpan:
    """The disabled-tracer span: a shared, stateless no-op.

    ``__enter__``/``__exit__`` do nothing — no clock read, no allocation —
    so a disabled tracer's per-call cost is one attribute check plus
    returning this singleton.
    """

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def note(self, **args: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


def _never() -> bool:
    return False


class _Span:
    """One live span: records a Chrome ``"X"`` (complete) event on exit."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        if tracer._annotate:
            # pass-through into the device profile: the SAME name shows up
            # in the jax.profiler trace, which is what lets profile.py
            # align the host and device clocks
            ann = tracer._trace_annotation
            if ann is not None:
                # scalar args ride along, so the capture's host plane
                # shows uid/trace/active beside the name
                self._annotation = ann(self._name, **{
                    k: v for k, v in self._args.items()
                    if isinstance(v, (str, int, float))
                })
                self._annotation.__enter__()
        self._t0 = time.perf_counter()
        tracer._depth_local.depth = getattr(tracer._depth_local, "depth", 0) + 1
        return self

    def note(self, **args: Any) -> None:
        """Args the span records when it closes, beside (or over) those it
        opened with; the capture's host plane has only those."""
        self._args.update(args)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        tracer = self._tracer
        depth = getattr(tracer._depth_local, "depth", 1)
        tracer._depth_local.depth = depth - 1
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        ctx = tracer._context
        args = {**ctx, **self._args} if ctx else (
            dict(self._args) if self._args else {}
        )
        args["depth"] = depth - 1  # 0 = top-level: span nesting, testable
        tracer._appended += 1
        tracer._events.append(
            {
                "ph": "X",
                "name": self._name,
                "cat": self._cat,
                "pid": tracer.pid,
                "tid": threading.get_ident() & 0xFFFFFFFF,
                "ts": (self._t0 - tracer._epoch_perf) * 1e6,
                "dur": (t1 - self._t0) * 1e6,
                "args": args,
            }
        )
        rec = tracer._recorder
        if rec is PROCESS_RECORDER:
            rec = _recorder_mod._RECORDER
        if rec is not None and rec.enabled:
            # the flight recorder shadows the enabled tracer too: the ring
            # must hold the LAST spans regardless of which driver is on
            rec.record(
                "span", self._name, self._cat, self._t0,
                (t1 - self._t0) * 1e6, self._args,
            )


class Tracer:
    """Nested host spans + instant events on one monotonic clock.

    Thread-safe by construction: events append to one bounded deque
    (atomic under the GIL) and nesting depth is tracked per thread, so the
    scheduler loop, the trainer loop and the watchdog thread can all
    report into the same tracer.  It records while ``enabled`` OR while
    any ``jax.profiler`` capture is live (``annotate=False`` opts out of
    both the pass-through and the following).
    """

    def __init__(
        self,
        *,
        enabled: bool = False,
        annotate: bool = True,
        pid: Optional[int] = None,
        process_name: Optional[str] = None,
        recorder: Optional[FlightRecorder] = None,
        max_events: int = MAX_EVENTS,
    ):
        self._enabled = enabled
        self._annotate_requested = annotate
        self._annotate = False
        self._trace_annotation = None
        # "is a jax.profiler capture live?" — the unbound probe until
        # jax is importable-without-cost (already in sys.modules), then
        # TraceAnnotation.is_enabled itself
        self._capture_live = (
            self._probe_capture if annotate else _never
        )
        # pid/process_name derive from the EXPORTING process (the old
        # hardcoded pid-1 interleaved every fleet worker's spans into one
        # track when shards merged); ``process_name`` overrides for
        # replica naming (``replica-3`` instead of ``ddlt-host``)
        self.pid = int(pid) if pid is not None else os.getpid()
        self.process_name = (
            process_name if process_name is not None else "ddlt-host"
        )
        # default args stamped onto every span/event (fleet workers set
        # replica=k so every scheduler span carries its replica identity)
        self._context: Dict[str, Any] = {}
        self._recorder = recorder
        self._events: deque = deque(maxlen=int(max_events))
        self._appended = 0  # events ever appended; dropped = this - kept
        self._depth_local = threading.local()
        # epoch pair: perf_counter for span math, wall clock so merged
        # timelines can be stamped in absolute time (and so fleet shards
        # can be aligned onto the router clock)
        self._epoch_perf = time.perf_counter()
        self._epoch_wall = time.time()
        if enabled:
            self._resolve_annotation()

    def _resolve_annotation(self) -> None:
        """Bind ``jax.profiler.TraceAnnotation`` lazily — the registry and
        schema halves of ``obs`` stay importable without jax."""
        if not self._annotate_requested or self._trace_annotation is not None:
            return
        try:
            from jax.profiler import TraceAnnotation

            self._trace_annotation = TraceAnnotation
            self._capture_live = TraceAnnotation.is_enabled
            self._annotate = True
        except Exception:  # pragma: no cover - jax always present in-repo
            self._annotate = False
            self._capture_live = _never

    def _probe_capture(self) -> bool:
        """The capture probe before ``TraceAnnotation`` is bound: no
        capture can be live in a process that has not imported jax, and
        the tracer never imports it on a hot path's behalf (a fleet
        router stays off jax).  Once jax is there, bind and ask."""
        if "jax" not in sys.modules:
            return False
        self._resolve_annotation()
        return self._capture_live()

    # -- control ----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def recording(self) -> bool:
        """Whether a span opened now lands in ``events``: enabled, or a
        ``jax.profiler`` capture is live."""
        return self._enabled or self._capture_live()

    @property
    def epoch_unix_s(self) -> float:
        """Wall-clock time of this tracer's perf_counter epoch — the
        anchor fleet shard merging aligns worker clocks with."""
        return self._epoch_wall

    @property
    def epoch_perf_s(self) -> float:
        """``time.perf_counter()`` at this tracer's epoch: an event's
        ``ts`` is microseconds after it, so a reader can put events on
        another clock from two ``perf_counter`` readings of its own."""
        return self._epoch_perf

    @property
    def dropped(self) -> int:
        """Events pushed out of the bounded list since the last clear."""
        return self._appended - len(self._events)

    def enable(self) -> "Tracer":
        self._enabled = True
        self._resolve_annotation()
        return self

    def disable(self) -> "Tracer":
        self._enabled = False
        return self

    def clear(self) -> None:
        self._events.clear()
        self._appended = 0

    def set_context(self, **args: Any) -> "Tracer":
        """Merge default args stamped onto every subsequent span/event —
        the fleet worker sets ``replica=k`` once instead of threading it
        through every instrumentation site."""
        self._context.update(args)
        return self

    def attach_recorder(
        self, recorder: Optional[FlightRecorder]
    ) -> "Tracer":
        """Attach (or detach with None) a flight recorder: spans/events
        then land in its ring even while the tracer is disabled."""
        self._recorder = recorder
        return self

    # -- recording --------------------------------------------------------
    def span(self, name: str, cat: str = "host", **args):
        """Context manager timing a host-side phase.  Records while
        enabled or while a ``jax.profiler`` capture is live.  Otherwise,
        without a recorder: the shared no-op span (no clock read, no
        allocation).  With a flight recorder attached the disabled path
        hands out the recorder's lightweight span instead — one ring
        append, still zero-sync (lint-pinned)."""
        if self._enabled or self._capture_live():
            return _Span(self, name, cat, args)
        rec = self._recorder
        if rec is PROCESS_RECORDER:
            rec = _recorder_mod._RECORDER
        if rec is not None and rec.enabled:
            return _RecorderSpan(rec, name, cat, args)
        return _NULL_SPAN

    def event(self, name: str, cat: str = "host", **args) -> None:
        """Instant event (Chrome ``"i"``): watchdog trips, preemptions,
        anomaly detections — point-in-time marks on the same timeline.
        Recorded into the attached flight recorder even when disabled;
        into the tracer while enabled or while a capture is live."""
        rec = self._recorder
        if rec is PROCESS_RECORDER:
            rec = _recorder_mod._RECORDER
        if rec is not None and rec.enabled:
            rec.record_event(name, cat, args)
        if not (self._enabled or self._capture_live()):
            return
        ctx = self._context
        self._appended += 1
        self._events.append(
            {
                "ph": "i",
                "s": "t",  # thread-scoped instant
                "name": name,
                "cat": cat,
                "pid": self.pid,
                "tid": threading.get_ident() & 0xFFFFFFFF,
                "ts": (time.perf_counter() - self._epoch_perf) * 1e6,
                "args": {**ctx, **args} if ctx else dict(args),
            }
        )

    # -- export -----------------------------------------------------------
    @property
    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The ``{"traceEvents": [...]}`` Chrome/Perfetto container, with
        process metadata naming the host lane.  pid/process_name come
        from THIS process (a fleet worker's shard renders as its own
        track when merged — the old hardcoded pid collapsed every
        exporting process into one), and ``metadata.host_pids`` records
        which pids are host-tracer lanes so the merge/digest layers never
        have to guess from magic numbers."""
        meta = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": self.pid,
                "args": {"name": self.process_name},
            }
        ]
        return {
            "traceEvents": meta + list(self._events),
            "displayTimeUnit": "ms",
            "metadata": {
                "tracer_epoch_unix_s": self._epoch_wall,
                "clock": "perf_counter us since tracer epoch",
                "host_pids": [self.pid],
                "process_name": self.process_name,
                "dropped": self.dropped,
            },
        }

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
        return path


# -- process-global tracer (disabled by default) --------------------------
# The process tracer carries the process flight recorder (resolved
# dynamically via the sentinel, so set_recorder swaps apply): spans and
# events on the global tracer land in the bounded ring even while
# tracing is off — that ring is what the watchdog/quarantine/death
# dumps freeze.

_TRACER = Tracer(enabled=False, recorder=PROCESS_RECORDER)


#: the collection in progress, as a span (collections never overlap)
_gc_span: Optional[_Span] = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """The ``gc.callbacks`` hook: while the process tracer records, each
    collection is a ``host/gc`` span with its ``generation`` and the objects
    it ``collected``; otherwise it returns at its first test.  It never
    binds the capture probe (a collection can land inside the import of jax
    itself), so a capture is followed once a span has bound it."""
    global _gc_span
    tracer = _TRACER
    if phase == "start":
        if not (
            isinstance(tracer, Tracer)
            and (
                tracer._enabled
                or (tracer._annotate and tracer._capture_live())
            )
        ):
            return
        span = _Span(tracer, "host/gc", "host",
                     {"generation": info["generation"]})
        span.__enter__()
        _gc_span = span
    elif _gc_span is not None:
        span, _gc_span = _gc_span, None
        span.note(collected=info["collected"])
        span.__exit__(None, None, None)


# one hook a process: a reload of this module replaces its predecessor's
gc.callbacks[:] = [
    hook for hook in gc.callbacks
    if (getattr(hook, "__module__", None), getattr(hook, "__name__", None))
    != (__name__, "_on_gc")
] + [_on_gc]


def get_tracer() -> Tracer:
    """The process's tracer.  Records nothing until a driver — ``ddlt
    obs``, ``bench.py --obs``, ``--trace-dir`` — enables it, or while a
    ``jax.profiler`` capture is live."""
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    _TRACER = tracer
    return tracer


def configure(
    *,
    enabled: bool,
    annotate: bool = True,
    pid: Optional[int] = None,
    process_name: Optional[str] = None,
) -> Tracer:
    """Install a fresh tracer with the given switches and return it (the
    process flight recorder stays attached, resolved dynamically)."""
    return set_tracer(
        Tracer(
            enabled=enabled, annotate=annotate, pid=pid,
            process_name=process_name, recorder=PROCESS_RECORDER,
        )
    )
