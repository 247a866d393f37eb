"""Deterministic, step-keyed fault injection — chaos you can unit-test.

A resilience layer that is never exercised is dead code (the reference's
resume protocol literally was — SURVEY §5).  This module turns the failure
modes that dominate pod-scale training into *injectable, reproducible*
events so every recovery path runs on CPU in tier-1 tests and in
``bench.py --faults``:

    DDLT_FAULTS="nan_loss@12,data_stall@30:secs=2,preempt@50,io_error@p=0.05:seed=7"

Grammar (comma-separated entries)::

    <kind>@<step>[:key=val]...      step-keyed, fires ONCE at true step N
    <kind>@p=<prob>[:key=val]...    probabilistic per opportunity, seeded

Kinds:

- ``nan_loss``   poison the float arrays of the batch feeding step N with
                 NaN → the jitted step's non-finite guard and the host-side
                 :class:`~..train.resilience.AnomalyDetector` must react
                 (needs a float input key; token-only LM batches have none);
- ``data_stall`` the data iterator sleeps ``secs`` (default 1.0) before
                 yielding the batch for step N — watchdog fodder;
- ``data_death`` the data iterator raises ``DataStreamDeath`` instead of
                 yielding step N's batch — the mid-epoch input-stream crash
                 a supervisor restart must survive;
- ``preempt``    the :class:`PreemptionGuard` is triggered during step N,
                 exactly as if SIGTERM had arrived — emergency checkpoint +
                 resumable exit;
- ``io_error``   storage writes (checkpoint save/wait, metrics appends)
                 raise ``InjectedIOError`` with probability ``p`` (seeded,
                 so a given seed produces the same failure sequence) — the
                 retry layer's test harness.  The ``@N`` form fires once at
                 the **Nth storage opportunity** (storage sites have no
                 train-step context), NOT at true step N.

Serve-side kinds (PR 7 — consumed by ``serve/scheduler`` and the fleet
supervisor in ``serve/fleet``; their ``@N`` is the scheduler's **decode
step** counter, 1-based, per worker process):

- ``replica_death`` the fleet worker hard-exits (``os._exit``) at decode
                 step N — no drain, no goodbye; the router must detect the
                 death, restart the replica, and requeue its in-flight
                 requests onto survivors;
- ``decode_nan``   one active request's K-cache history is poisoned with
                 NaN at the first decode step >= N that has an eligible
                 victim (a slot that has decoded at least one token, so
                 the poison lands in a decode-written — never shared —
                 cache region): the scheduler's quarantine must fail ONLY
                 that request;
- ``decode_stall`` the decode dispatch sleeps ``secs`` (default 1.0) at
                 the first decode step >= N — scheduler-watchdog fodder;
- ``reject_admit`` admission rejects the request with probability ``p``
                 (or once at the Nth admission opportunity) — the
                 overload-shedding path; the request finishes ``"shed"``
                 and the fleet router redelivers it elsewhere.

Traffic-shaping kinds (consumed by ``serve/traffic.py`` at schedule
build — their ``@N`` is the **Nth matching schedule-build opportunity**,
one per tenant per :meth:`~..serve.traffic.TrafficGenerator.schedule`
call, because traffic generation has no step context; a ``tenant=``
option restricts matching to that tenant's builds):

- ``burst``      splice an extra poisson arrival burst into the matched
                 tenant's schedule — ``rps=`` (burst rate, default 4x the
                 tenant's base rate), ``secs=`` (burst length, default
                 1.0), ``at=`` (start offset, default 0.0).  The overload
                 bench's misbehaving-client injection;
- ``slow_tenant`` multiply the matched tenant's prompt lengths (and its
                 per-request token budget, when the spec sets one) by
                 ``factor=`` (default 4.0) — the straggler-tenant shape.

Checkpoint durability kinds (consumed by ``train/checkpoint.py`` — their
``@N`` is **generation-opportunity**-keyed, like ``io_error``'s, because
storage finalization has no train-step context):

- ``ckpt_corrupt`` corrupt the Nth FINALIZED checkpoint generation right
                 after its manifest lands — ``:mode=`` picks how: ``flip``
                 (one byte of the largest data file), ``truncate`` (cut it
                 in half), ``unlink`` (delete it), ``manifest`` (delete
                 the manifest itself).  The verified-restore path must
                 fall back to the newest older generation that still
                 verifies;
- ``ckpt_torn``  kill the writer mid-generation: the Nth save finalize
                 truncates a data file and never writes its manifest —
                 the generation is by-construction incomplete and must
                 never be restore-eligible.

The serve step-keyed kinds use **at-or-after** matching (first decode
step ``>= N``): decode steps are contiguous per worker, but ``decode_nan``
must wait for an eligible victim, and at-or-after keeps the whole family
deterministic under that gating.

Step numbering for the train/data kinds is the framework's **true step**:
the step whose completion sets ``state.step == N`` (the same numbering
checkpoints use), 1-based.

Faults are **one-shot per process**: the plan is a process-level singleton
(:func:`get_plan`) that survives in-process supervisor restarts, so a
``preempt@50`` fires once and the resumed attempt runs past step 50 instead
of preempting forever.  :func:`reset` re-arms (new CLI invocation, tests).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import time
from typing import Any, Dict, Iterator, List, Optional

logger = logging.getLogger("ddlt.faults")

ENV_VAR = "DDLT_FAULTS"

KINDS = (
    "nan_loss", "data_stall", "data_death", "preempt", "io_error",
    "replica_death", "decode_nan", "decode_stall", "reject_admit",
    "ckpt_corrupt", "ckpt_torn", "burst", "slow_tenant",
)

#: kinds the serving stack consumes — the fleet supervisor DEALS these
#: across replica workers (see :func:`deal_serve_faults`) instead of
#: letting every worker's inherited environment fire all of them
SERVE_KINDS = ("replica_death", "decode_nan", "decode_stall", "reject_admit")


class InjectedIOError(IOError):
    """A storage failure injected by an ``io_error`` fault."""


class DataStreamDeath(RuntimeError):
    """The input stream died mid-epoch (``data_death`` fault, or real)."""

    def __init__(self, msg: str, *, step: Optional[int] = None):
        super().__init__(msg)
        self.step = step


@dataclasses.dataclass
class FaultSpec:
    kind: str
    step: Optional[int] = None       # step-keyed trigger (1-based true step)
    prob: Optional[float] = None     # probabilistic trigger
    options: Dict[str, Any] = dataclasses.field(default_factory=dict)
    fired: bool = False              # one-shot bookkeeping (step-keyed only)

    def describe(self) -> str:
        trig = f"@{self.step}" if self.step is not None else f"@p={self.prob}"
        opts = "".join(f":{k}={v}" for k, v in self.options.items())
        return f"{self.kind}{trig}{opts}"


def parse_spec(text: str) -> List[FaultSpec]:
    """Parse the ``DDLT_FAULTS`` grammar; raises ValueError on bad entries."""
    specs: List[FaultSpec] = []
    for raw in text.split(","):
        raw = raw.strip()
        if not raw:
            continue
        head, *opt_parts = raw.split(":")
        if "@" not in head:
            raise ValueError(
                f"fault entry {raw!r} missing '@<step>' or '@p=<prob>'"
            )
        kind, trigger = head.split("@", 1)
        kind = kind.strip()
        if kind not in KINDS:
            raise ValueError(
                f"unknown fault kind {kind!r}; known: {', '.join(KINDS)}"
            )
        options: Dict[str, Any] = {}
        for part in opt_parts:
            if "=" not in part:
                raise ValueError(f"fault option {part!r} is not key=val")
            k, v = part.split("=", 1)
            try:
                options[k] = int(v)
            except ValueError:
                try:
                    options[k] = float(v)
                except ValueError:
                    options[k] = v
        if trigger.startswith("p="):
            prob = float(trigger[2:])
            if not 0.0 <= prob <= 1.0:
                raise ValueError(f"fault probability {prob} outside [0, 1]")
            specs.append(FaultSpec(kind=kind, prob=prob, options=options))
        else:
            step = int(trigger)
            if step < 1:
                raise ValueError(
                    f"fault step {step} must be >= 1 (true-step numbering)"
                )
            specs.append(FaultSpec(kind=kind, step=step, options=options))
    return specs


@dataclasses.dataclass
class FaultEvent:
    kind: str
    step: Optional[int]
    site: str
    at: float


class FaultPlan:
    """A parsed fault schedule plus firing bookkeeping.

    Falsy when empty, so hot loops can gate on ``if plan:`` and pay nothing
    in the no-fault case.
    """

    def __init__(self, specs: Optional[List[FaultSpec]] = None):
        self.specs = specs or []
        self.events: List[FaultEvent] = []
        self._rngs: Dict[int, random.Random] = {}
        self._io_opportunities: Dict[int, int] = {}  # per-spec call counter

    def __bool__(self) -> bool:
        return bool(self.specs)

    @classmethod
    def from_env(cls, env: Optional[Dict[str, str]] = None) -> "FaultPlan":
        text = (env if env is not None else os.environ).get(ENV_VAR, "")
        return cls(parse_spec(text)) if text else cls()

    # -- firing ----------------------------------------------------------

    def _record(self, spec: FaultSpec, step: Optional[int], site: str) -> None:
        self.events.append(
            FaultEvent(kind=spec.kind, step=step, site=site, at=time.time())
        )
        logger.warning(
            "FAULT INJECTED: %s at step %s (%s)", spec.describe(), step, site
        )
        # every injected fault lands in the flight-recorder ring too, so
        # a dump triggered moments later shows the injection next to its
        # consequences (lazy import: faults is a leaf utility)
        try:
            from distributeddeeplearning_tpu.obs.recorder import get_recorder

            get_recorder().record_event(
                f"fault/{spec.kind}", "fault", {"step": step, "site": site}
            )
        except Exception:  # pragma: no cover - recording must never fault
            pass

    def _take_step_keyed(self, kind: str, step: int) -> Optional[FaultSpec]:
        """Consume the one-shot step-keyed ``kind`` fault for ``step``."""
        for spec in self.specs:
            if spec.kind == kind and spec.step == step and not spec.fired:
                spec.fired = True
                self._record(spec, step, kind)
                return spec
        return None

    def _take_at_or_after(self, kind: str, step: int) -> Optional[FaultSpec]:
        """Consume the one-shot ``kind`` fault armed for any step <= ``step``
        (at-or-after matching — the serve decode-step kinds, see module
        docstring)."""
        for spec in self.specs:
            if (
                spec.kind == kind
                and spec.step is not None
                and spec.step <= step
                and not spec.fired
            ):
                spec.fired = True
                self._record(spec, step, kind)
                return spec
        return None

    def _prob_fires(self, spec: FaultSpec, site: str) -> bool:
        rng = self._rngs.setdefault(
            id(spec), random.Random(int(spec.options.get("seed", 0)))
        )
        if rng.random() < (spec.prob or 0.0):
            self._record(spec, None, site)
            return True
        return False

    # -- hook: train step ------------------------------------------------

    def poison_batch(self, step: int, batch):
        """``nan_loss``: NaN-fill the float arrays of step N's batch.

        Integer arrays (token ids, labels) pass through untouched; a batch
        with no float leaf raises loudly — the fault would otherwise be a
        silent no-op and the test asserting recovery would pass vacuously.
        """
        import numpy as np

        if self._take_step_keyed("nan_loss", step) is None:
            return batch
        poisoned = dict(batch)
        hit = False
        for key, arr in poisoned.items():
            a = np.asarray(arr)
            if np.issubdtype(a.dtype, np.floating):
                poisoned[key] = np.full_like(a, np.nan)
                hit = True
        if not hit:
            raise ValueError(
                "nan_loss fault fired but the batch has no float array to "
                f"poison (keys: {sorted(batch)}); token-only workloads "
                "cannot express this fault"
            )
        return poisoned

    def maybe_preempt(self, step: int, guard) -> bool:
        """``preempt``: trigger ``guard`` as if SIGTERM arrived at step N."""
        spec = self._take_step_keyed("preempt", step)
        if spec is None:
            return False
        guard.trigger(reason=f"injected preempt@{step}")
        return True

    # -- hook: data iterator ---------------------------------------------

    def wrap_data(self, batches: Iterator, *, start_step: int = 0) -> Iterator:
        """Apply ``data_stall`` / ``data_death`` to a batch stream.

        The batch yielded ``i``-th feeds true step ``start_step + i + 1`` —
        the same numbering the step-keyed triggers use.
        """
        if not any(s.kind in ("data_stall", "data_death") for s in self.specs):
            return batches

        def wrapped():
            step = start_step
            for batch in batches:
                step += 1
                spec = self._take_step_keyed("data_death", step)
                if spec is not None:
                    raise DataStreamDeath(
                        f"injected data_death@{step}", step=step
                    )
                spec = self._take_step_keyed("data_stall", step)
                if spec is not None:
                    time.sleep(float(spec.options.get("secs", 1.0)))
                yield batch

        return wrapped()

    # -- hook: serve scheduler / fleet worker ----------------------------

    def take_replica_death(self, step: int) -> bool:
        """``replica_death``: True when the worker should hard-exit NOW
        (first decode step >= the armed step)."""
        return self._take_at_or_after("replica_death", step) is not None

    def acts_on_decode_steps(self) -> bool:
        """True while a ``decode_nan`` or ``decode_stall`` is still armed:
        the scheduler then keeps no decode step in flight, so the fault
        lands between one step's read and the next step's dispatch."""
        return any(
            s.kind in ("decode_nan", "decode_stall") and not s.fired
            for s in self.specs
        )

    def take_decode_stall(self, step: int) -> Optional[float]:
        """``decode_stall``: seconds to sleep before this decode step's
        dispatch, or None."""
        spec = self._take_at_or_after("decode_stall", step)
        if spec is None:
            return None
        return float(spec.options.get("secs", 1.0))

    def has_decode_nan(self, step: int) -> bool:
        """Non-consuming peek: a ``decode_nan`` is armed for step <= N.

        The scheduler peeks first because the fault needs an eligible
        victim (a slot with at least one decode-written position — see
        module docstring); with none active the fault stays armed for the
        next step instead of being burned on a no-op."""
        return any(
            s.kind == "decode_nan"
            and s.step is not None
            and s.step <= step
            and not s.fired
            for s in self.specs
        )

    def take_decode_nan(self, step: int) -> bool:
        """Consume the armed ``decode_nan`` (call only with a victim)."""
        return self._take_at_or_after("decode_nan", step) is not None

    def maybe_reject_admit(self) -> bool:
        """``reject_admit``: True when THIS admission opportunity must be
        rejected (probabilistic ``@p=`` — seeded — or one-shot at the Nth
        admission opportunity for the ``@N`` form)."""
        for spec in self.specs:
            if spec.kind != "reject_admit":
                continue
            if spec.prob is not None:
                if self._prob_fires(spec, "reject_admit"):
                    return True
            elif not spec.fired:
                n = self._io_opportunities.get(id(spec), 0) + 1
                self._io_opportunities[id(spec)] = n
                if n >= (spec.step or 1):
                    spec.fired = True
                    self._record(spec, spec.step, "reject_admit")
                    return True
        return False

    # -- hook: traffic generation (serve/traffic.py) ---------------------

    def _take_tenant_keyed(
        self, kind: str, tenant: str
    ) -> Optional[Dict[str, Any]]:
        """Consume a one-shot ``kind`` fault at its Nth MATCHING
        schedule-build opportunity: a ``tenant=`` option restricts
        matching (and opportunity counting) to that tenant's builds, so
        ``burst@1:tenant=best_effort`` fires on the best_effort tenant
        regardless of tenant iteration order."""
        for spec in self.specs:
            if spec.kind != kind or spec.fired:
                continue
            want = spec.options.get("tenant")
            if want is not None and str(want) != tenant:
                continue
            n = self._io_opportunities.get(id(spec), 0) + 1
            self._io_opportunities[id(spec)] = n
            if n >= (spec.step or 1):
                spec.fired = True
                self._record(spec, spec.step, f"{kind}:{tenant}")
                return dict(spec.options)
        return None

    def take_burst(self, tenant: str) -> Optional[Dict[str, Any]]:
        """``burst``: overload-injection options for THIS tenant's
        schedule build (``rps`` / ``secs`` / ``at`` — see module
        docstring), else None."""
        return self._take_tenant_keyed("burst", tenant)

    def take_slow_tenant(self, tenant: str) -> Optional[Dict[str, Any]]:
        """``slow_tenant``: straggler-injection options for THIS tenant's
        schedule build (``factor`` — see module docstring), else None."""
        return self._take_tenant_keyed("slow_tenant", tenant)

    # -- hook: storage paths ---------------------------------------------

    def maybe_io_error(self, site: str) -> None:
        """``io_error``: raise :class:`InjectedIOError` at a storage call.

        The ``@N`` form is opportunity-keyed (fires once, at the Nth
        ``maybe_io_error`` call across all storage sites): the storage
        paths have no train-step context, so true-step keying is not
        expressible here — see the module docstring.
        """
        for spec in self.specs:
            if spec.kind != "io_error":
                continue
            if spec.prob is not None:
                if self._prob_fires(spec, site):
                    raise InjectedIOError(f"injected io_error ({site})")
            elif not spec.fired:
                n = self._io_opportunities.get(id(spec), 0) + 1
                self._io_opportunities[id(spec)] = n
                if n >= (spec.step or 1):
                    spec.fired = True
                    self._record(spec, spec.step, site)
                    raise InjectedIOError(f"injected io_error ({site})")

    # -- hook: checkpoint durability (train/checkpoint.py) ---------------

    def _take_nth_opportunity(
        self, kind: str, site: str
    ) -> Optional[FaultSpec]:
        """Consume a one-shot ``kind`` fault at its Nth opportunity (the
        per-spec call counter — the same keying ``io_error@N`` uses,
        because storage paths have no train-step context)."""
        for spec in self.specs:
            if spec.kind != kind or spec.fired:
                continue
            n = self._io_opportunities.get(id(spec), 0) + 1
            self._io_opportunities[id(spec)] = n
            if n >= (spec.step or 1):
                spec.fired = True
                self._record(spec, spec.step, site)
                return spec
        return None

    def take_ckpt_corrupt(self) -> Optional[Dict[str, Any]]:
        """``ckpt_corrupt``: options dict (``mode`` etc.) when THIS
        checkpoint-generation finalize must corrupt the generation it
        just committed, else None.  Opportunity-keyed: ``@N`` fires at
        the Nth finalized generation of the process."""
        spec = self._take_nth_opportunity("ckpt_corrupt", "ckpt_corrupt")
        return dict(spec.options) if spec is not None else None

    def take_ckpt_torn(self) -> bool:
        """``ckpt_torn``: True when THIS save finalize must tear the
        generation (truncate a data file, never write the manifest) —
        the writer-died-mid-generation failure mode."""
        return (
            self._take_nth_opportunity("ckpt_torn", "ckpt_torn") is not None
        )

    # -- reporting -------------------------------------------------------

    def report(self) -> List[Dict[str, Any]]:
        return [
            {"kind": e.kind, "step": e.step, "site": e.site}
            for e in self.events
        ]


# -- fleet helpers: dealing a spec across replica workers -----------------


def deal_serve_faults(text: str, n_replicas: int) -> List[str]:
    """Split a ``DDLT_FAULTS`` spec into one per-replica spec string.

    Serve-side entries (:data:`SERVE_KINDS`) go to exactly ONE replica —
    an explicit ``:replica=k`` option wins, otherwise serve entries are
    dealt round-robin in spec order — because every spawned worker
    re-parses its environment: without dealing, ``replica_death@3`` would
    kill EVERY replica at its own step 3 and leave no survivor to requeue
    onto.  Non-serve entries (``io_error`` etc.) replicate to all workers.
    """
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    dealt: List[List[str]] = [[] for _ in range(n_replicas)]
    serve_i = 0
    for spec in parse_spec(text or ""):
        if spec.kind in SERVE_KINDS:
            if "replica" in spec.options:
                target = int(spec.options["replica"]) % n_replicas
            else:
                target = serve_i % n_replicas
                serve_i += 1
            dealt[target].append(spec.describe())
        else:
            for entries in dealt:
                entries.append(spec.describe())
    return [",".join(entries) for entries in dealt]


def strip_kinds(text: str, kinds) -> str:
    """Drop every entry of the given kinds from a spec string — the fleet
    supervisor strips ``replica_death`` from a RESTARTED replica's spec so
    an injected death is not replayed forever (the restarted process would
    otherwise re-parse the same spec and die at its own step N again)."""
    kept = [s.describe() for s in parse_spec(text or "") if s.kind not in kinds]
    return ",".join(kept)


# -- process-level plan (one-shot across in-process restarts) ------------

_PLAN: Optional[FaultPlan] = None


def get_plan() -> FaultPlan:
    """The process's active plan, parsed from ``DDLT_FAULTS`` on first use."""
    global _PLAN
    if _PLAN is None:
        _PLAN = FaultPlan.from_env()
        if _PLAN:
            logger.warning(
                "fault injection ACTIVE: %s",
                ", ".join(s.describe() for s in _PLAN.specs),
            )
    return _PLAN


def reset() -> FaultPlan:
    """Re-parse ``DDLT_FAULTS`` and re-arm every fault (tests, new runs)."""
    global _PLAN
    _PLAN = None
    return get_plan()


def install_plan(text: str) -> FaultPlan:
    """Install an explicit spec as THE process plan, ignoring the
    environment — fleet workers use this so the per-replica spec their
    supervisor dealt them overrides the full ``DDLT_FAULTS`` they
    inherited at spawn (which would otherwise fire every entry in every
    worker)."""
    global _PLAN
    _PLAN = FaultPlan(parse_spec(text or ""))
    if _PLAN:
        logger.warning(
            "fault injection ACTIVE (installed): %s",
            ", ".join(s.describe() for s in _PLAN.specs),
        )
    return _PLAN
