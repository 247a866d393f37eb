"""Profiling harness: device trace + host spans -> one merged timeline.

The attribution layer the QUANT_r10 regression exposed a need for: int8
decode is slower than f32 and nothing could say WHERE the dequant cost
lands.  This module answers it three ways, composed by ``bench.py --obs``
and ``ddlt obs``:

- :func:`run_profiled` wraps any host callable with the obs tracer AND
  ``jax.profiler.trace`` so the two record the same window;
- :func:`merge_host_device` aligns the ``jax.profiler`` trace file onto
  the host tracer's clock (the tracer's TraceAnnotation pass-through
  plants identical span names in both, which gives the offset) and emits
  one Chrome-trace JSON — train steps, serve request lifecycles,
  resilience events and device activity on one timeline;
- :func:`decode_phase_breakdown` decomposes a serving engine's decode
  step into measured phases (page gather, scale dequant, the
  attention/MLP residual) by timing jitted phase programs over the
  engine's LIVE cache — platform-independent attribution that works even
  where the profiler emits no per-HLO device events (CPU), with
  :func:`device_analysis` layering the roofline per-op table on top when
  the trace carries XLA cost-model annotations (TPU).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from distributeddeeplearning_tpu.obs.trace import Tracer, get_tracer

logger = logging.getLogger("ddlt.obs.profile")

__all__ = [
    "run_profiled",
    "profile_and_merge",
    "load_device_trace",
    "merge_host_device",
    "summarize_timeline",
    "device_analysis",
    "decode_phase_breakdown",
    "attribute_regression",
]


def run_profiled(
    fn: Callable[[], Any],
    *,
    trace_dir: str,
    tracer: Optional[Tracer] = None,
) -> Tuple[Any, Tracer]:
    """Run ``fn()`` with the tracer enabled inside ``jax.profiler.trace``.

    Returns ``(fn's result, the tracer)`` — feed both to
    :func:`merge_host_device` for the combined timeline.  The tracer is
    enabled for the duration and restored to its prior state after.
    """
    import jax

    tracer = tracer if tracer is not None else get_tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    try:
        with jax.profiler.trace(trace_dir):
            with tracer.span("profile/window"):
                result = fn()
    finally:
        if not was_enabled:
            tracer.disable()
    return result, tracer


def profile_and_merge(
    fn: Callable[[], Any],
    *,
    trace_dir: str,
    tracer: Optional[Tracer] = None,
) -> Tuple[Any, Tracer, Dict[str, Any], str]:
    """The whole profile-run choreography every driver shares.

    :func:`run_profiled` (enable → profiler window → restore, exception-
    safe) followed by :func:`merge_host_device`, with the merged
    Chrome-trace written to ``<trace_dir>/merged.trace.json``.  Returns
    ``(fn's result, tracer, merged trace, merged path)`` — one call site
    for ``ddlt serve --trace-dir``, ``ddlt obs`` and ``bench.py --obs``,
    so the output name and JSON framing cannot drift between them.
    """
    import json
    import os

    os.makedirs(trace_dir, exist_ok=True)
    result, tracer = run_profiled(fn, trace_dir=trace_dir, tracer=tracer)
    merged = merge_host_device(tracer, trace_dir)
    merged_path = os.path.join(trace_dir, "merged.trace.json")
    with open(merged_path, "w") as f:
        json.dump(merged, f)
        f.write("\n")
    return result, tracer, merged, merged_path


def load_device_trace(trace_dir: str) -> List[Dict[str, Any]]:
    """All events from the newest xprof trace file under ``trace_dir``
    (the raw side of the merge; [] when no trace file was written)."""
    from distributeddeeplearning_tpu.utils.roofline import (
        find_trace_file,
        load_trace_events,
    )

    try:
        trace_file = find_trace_file(trace_dir)
    except FileNotFoundError:
        return []
    return load_trace_events(trace_file)


def _alignment_offset_us(
    host_events: List[Dict[str, Any]], device_events: List[Dict[str, Any]]
) -> Optional[float]:
    """``host_ts - device_ts`` for the earliest span name present in both
    timelines (the TraceAnnotation pass-through guarantees shared names
    whenever the profiler captured the window).  None = no shared name."""
    device_by_name: Dict[str, float] = {}
    for ev in device_events:
        if ev.get("ph") == "X" and ev.get("name"):
            name = str(ev["name"])
            ts = float(ev.get("ts", 0.0))
            if name not in device_by_name or ts < device_by_name[name]:
                device_by_name[name] = ts
    best: Optional[float] = None
    best_host_ts: Optional[float] = None
    for ev in host_events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name")
        if name in device_by_name:
            host_ts = float(ev["ts"])
            if best_host_ts is None or host_ts < best_host_ts:
                best_host_ts = host_ts
                best = host_ts - device_by_name[name]
    return best


def merge_host_device(
    tracer: Tracer,
    trace_dir: Optional[str],
    *,
    keep_python_frames: bool = False,
) -> Dict[str, Any]:
    """One Chrome-trace container: host spans + the device profile, on the
    host clock.  Device events keep their own pids (the exported trace
    renders them as separate process rows); host spans live on pid 1
    ("ddlt-host").  Opens directly in chrome://tracing / Perfetto.

    xprof's host tracer records every Python frame as a ``$file:line``
    event — hundreds of thousands of them on a CPU run, drowning the
    rows that matter.  Those are dropped unless ``keep_python_frames``;
    XLA ops, runtime events and TraceAnnotations all stay.
    """
    merged = tracer.to_chrome_trace()
    device_events = load_device_trace(trace_dir) if trace_dir else []
    if device_events and not keep_python_frames:
        device_events = [
            e for e in device_events
            if not str(e.get("name", "")).startswith("$")
        ]
    if not device_events:
        merged["metadata"]["device_trace"] = "absent"
        return merged
    offset = _alignment_offset_us(merged["traceEvents"], device_events)
    merged["metadata"]["device_trace"] = "merged"
    merged["metadata"]["clock_offset_us"] = offset
    if offset is None:
        # no shared annotation (tracer ran outside the profiled window):
        # fall back to aligning the device trace's origin to the host's
        # first span — coarse, but the rows still land side by side
        offset = min(
            (
                float(e["ts"])
                for e in merged["traceEvents"]
                if e.get("ph") == "X"
            ),
            default=0.0,
        ) - min(
            (
                float(e.get("ts", 0.0))
                for e in device_events
                if e.get("ph") == "X"
            ),
            default=0.0,
        )
        merged["metadata"]["clock_offset_us"] = offset
        merged["metadata"]["clock_alignment"] = "coarse (no shared span name)"
    host_pids = set(merged.get("metadata", {}).get("host_pids") or [1])
    # keep host pids exclusive to tracer spans in the merge: a device
    # event landing on a host pid would interleave two processes into
    # one track (the same collision the fleet shard merge guards)
    remap = max(host_pids) + 1
    shifted = []
    for ev in device_events:
        ev = dict(ev)
        if ev.get("pid") in host_pids:
            ev["pid"] = remap
        if "ts" in ev:
            ev["ts"] = float(ev["ts"]) + offset
        shifted.append(ev)
    merged["traceEvents"] = merged["traceEvents"] + shifted
    return merged


def summarize_timeline(
    merged: Dict[str, Any], *, limit: int = 120
) -> Dict[str, Any]:
    """Artifact-sized digest of a merged timeline: per-source event
    counts, total duration per span name, and the ``limit`` longest
    events in chronological order (the full trace goes to disk, the
    digest goes in the JSON artifact)."""
    events = merged.get("traceEvents", [])
    # host lanes are whatever pids the tracer(s) stamped — recorded in
    # the container metadata (fleet merges union every shard's pid);
    # pid 1 is the pre-derived-pid fallback for old traces
    host_pids = set(merged.get("metadata", {}).get("host_pids") or [1])
    host = [
        e for e in events
        if e.get("ph") == "X" and e.get("pid") in host_pids
    ]
    device = [
        e for e in events
        if e.get("ph") == "X" and e.get("pid") not in host_pids
    ]
    instants = [e for e in events if e.get("ph") == "i"]
    by_name_ms: Dict[str, float] = {}
    for e in host:
        name = str(e.get("name"))
        by_name_ms[name] = by_name_ms.get(name, 0.0) + float(
            e.get("dur", 0.0)
        ) / 1e3
    top = sorted(
        host + device, key=lambda e: -float(e.get("dur", 0.0))
    )[:limit]
    top.sort(key=lambda e: float(e.get("ts", 0.0)))
    return {
        "event_counts": {
            "host_spans": len(host),
            "device_events": len(device),
            "instant_events": len(instants),
        },
        "host_span_total_ms": {
            name: round(ms, 3) for name, ms in sorted(
                by_name_ms.items(), key=lambda kv: -kv[1]
            )
        },
        "instant_events": [
            {
                "name": str(e.get("name")),
                "ts_ms": round(float(e.get("ts", 0.0)) / 1e3, 3),
                "args": e.get("args", {}),
            }
            for e in instants[:limit]
        ],
        "events": [
            {
                "name": str(e.get("name"))[:80],
                "source": (
                    "host" if e.get("pid") in host_pids else "device"
                ),
                "ts_ms": round(float(e.get("ts", 0.0)) / 1e3, 3),
                "dur_ms": round(float(e.get("dur", 0.0)) / 1e3, 3),
            }
            for e in top
        ],
    }


def device_analysis(trace_dir: str, *, steps: int) -> Dict[str, Any]:
    """The roofline per-op rollup, when the platform provides it.

    TPU traces carry XLA cost-model byte/FLOP annotations per HLO op;
    ``roofline.analyze_trace`` turns those into the per-category table.
    CPU traces carry none — that is reported as ``available: False`` with
    the reason, NOT an error: the phase breakdown below covers
    attribution there.
    """
    from distributeddeeplearning_tpu.utils.roofline import analyze_trace

    try:
        result = analyze_trace(trace_dir, steps=steps)
    except (FileNotFoundError, ValueError) as exc:
        return {"available": False, "reason": str(exc)}
    return {"available": True, **result}


# -- decode phase breakdown ------------------------------------------------

def _time_jitted(fn, args, *, iters: int, warmup: int = 2) -> float:
    """Mean seconds/call of a jitted thunk, post-warmup, synced."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def decode_phase_breakdown(
    engine, *, iters: int = 10, warmup: int = 2, spec_decoder=None
) -> Dict[str, Any]:
    """Measured per-phase decode cost of a paged serving engine.

    Four phases, each timed as its own jitted program over the engine's
    live cache and block tables (so the measured traffic is the decode
    step's real traffic):

    - ``page_gather``: gathering every slot's K/V history pages through
      the block tables — the cache-bandwidth sub-probe;
    - ``scale_dequant``: the int8 gather path's extra work — gather plus
      the per-(position, head) scale multiply materializing f32 history
      (measured as the increment over ``page_gather``; 0 on f32 engines);
    - ``attention_kernel``: the WHOLE per-step attention over the full
      cached history, all layers, through the engine's configured
      ``decode_kernel`` (``ops.flash_decode``) — the phase OBS_r11 could
      not see inside ``attention_mlp_other``, and the one a fused-kernel
      regression (or win) lands in;
    - ``mlp_other``: the decode step minus ``attention_kernel`` — qkv/
      proj/FF/head matmuls, sampling, dispatch.

    ``page_gather``/``scale_dequant`` are sub-probes OF the attention
    phase (the kernel's own cache reads), so the four phases are not
    additive; ``attention_kernel + mlp_other`` is the whole step.

    ``decode_step_ms`` is the real step (``engine.decode``), measured the
    same way the SERVE/QUANT artifacts measure it.

    With a ``spec_decoder`` (``spec.SpeculativeDecoder`` over this same
    engine) two more phases are measured from real spec steps over the
    live cache — ``draft`` (the K-dispatch draft chain) and ``verify``
    (the batched verify + readback) — plus the amortization they buy:
    ``spec_step_ms`` (draft + verify) and ``ms_per_committed_token``
    (spec step wall over tokens committed).  That last number is the one
    :func:`attribute_regression` needs to name an ACCEPTANCE-RATE
    collapse: when acceptance dies, ``draft``/``verify`` phase times
    barely move but every verify commits ~1 token, so the per-token cost
    balloons — the breakdown records ``tokens_per_verify`` so the
    attribution can say "the drafter stopped being believed", not just
    "decode got slower".
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.ops import flash_decode as fd
    from distributeddeeplearning_tpu.quant.qtensor import dequantize_kv

    cache = engine.cache
    tables = jnp.asarray(engine.block_tables)
    quantized = "k_scale" in cache

    def _gather(k, v, tbl):
        return k[tbl], v[tbl]

    gather_jit = jax.jit(_gather)
    t_gather = _time_jitted(
        gather_jit, (cache["k"], cache["v"], tables),
        iters=iters, warmup=warmup,
    )

    if quantized:
        def _gather_dequant(k, v, ks, vs, tbl):
            # the pool folds its heads into the minor axis; a scale names
            # one head of one position, so split the heads under it
            ks, vs = ks[tbl], vs[tbl]
            return (
                dequantize_kv(k[tbl].reshape(ks.shape + (-1,)), ks),
                dequantize_kv(v[tbl].reshape(vs.shape + (-1,)), vs),
            )

        t_dequant_inc = _time_jitted(
            jax.jit(_gather_dequant),
            (cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
             tables),
            iters=iters, warmup=warmup,
        )
        t_dequant = max(t_dequant_inc - t_gather, 0.0)
    else:
        t_dequant = 0.0

    # the whole attention phase: per-layer decode attention over the
    # LIVE cache at full-history positions through the engine's real
    # kernel path (fixed pseudo-random queries — the traffic, masking
    # and kernel dispatch are the step's own; only the q values differ)
    num_heads = engine.num_heads
    hd = cache["k"].shape[-1] // num_heads  # minor axis: h * hd
    L = cache["k"].shape[1]
    b = engine.batch_slots
    kernel = getattr(engine, "decode_kernel", "gather")
    page_size = engine.page_size
    key = jax.random.key(7)
    q_all = jax.random.normal(key, (L, b, num_heads, hd), jnp.float32)
    kt = jax.random.normal(
        jax.random.fold_in(key, 1), (b, num_heads, hd), jnp.float32
    )
    vt = jax.random.normal(
        jax.random.fold_in(key, 2), (b, num_heads, hd), jnp.float32
    )
    attn_pos = jnp.full((b,), engine.max_seq - 2, jnp.int32)

    def _attn_stack(k, v, ks, vs, tbl):
        def body(carry, xs):
            q, k_l, v_l, k_s, v_s = xs
            ctx = fd.decode_attention_paged(
                q, k_l, v_l, k_s, v_s, kt, vt, attn_pos, tbl,
                page_size=page_size, kernel=kernel,
            )
            return carry, ctx

        xs = (
            q_all,
            jnp.moveaxis(k, 1, 0),
            jnp.moveaxis(v, 1, 0),
            jnp.moveaxis(ks, 1, 0) if ks is not None else None,
            jnp.moveaxis(vs, 1, 0) if vs is not None else None,
        )
        _, ctxs = jax.lax.scan(body, 0, xs)
        return ctxs

    t_attention = _time_jitted(
        jax.jit(_attn_stack),
        (cache["k"], cache["v"], cache.get("k_scale"),
         cache.get("v_scale"), tables),
        iters=iters, warmup=warmup,
    )

    # the real decode step, same methodology as the serve benchmarks:
    # dispatch + compute + the sampled-token readback.  Positions sit at
    # the END of the window so attention spans the full cached history —
    # the steady-state, bandwidth-bound regime where the int8 dequant
    # cost actually lives (at position 1 there is no history to dequant
    # and the comparison would flatter int8).
    tokens = np.ones(engine.batch_slots, np.int32)
    pos = np.full(engine.batch_slots, engine.max_seq - 2, np.int32)
    for _ in range(warmup):
        engine.decode(tokens, pos)
    t0 = time.perf_counter()
    for _ in range(iters):
        engine.decode(tokens, pos)
    t_decode = (time.perf_counter() - t0) / iters

    residual = max(t_decode - t_attention, 0.0)
    phases_ms = {
        "page_gather": round(t_gather * 1e3, 3),
        "scale_dequant": round(t_dequant * 1e3, 3),
        "attention_kernel": round(t_attention * 1e3, 3),
        "mlp_other": round(residual * 1e3, 3),
    }
    total = max(t_decode, 1e-12)
    out = {
        "decode_step_ms": round(t_decode * 1e3, 3),
        "kv_dtype": engine.kv_dtype,
        "weights_dtype": engine.weights_dtype,
        "decode_kernel": kernel,
        "phases_ms": phases_ms,
        "phase_share_of_step": {
            name: round(ms / 1e3 / total, 4) for name, ms in phases_ms.items()
        },
        "iters": iters,
    }

    if spec_decoder is not None:
        # real spec steps over the live cache, same end positions as the
        # decode timing above — committed tokens measured, not assumed,
        # so an acceptance collapse shows up HERE as ms_per_committed_
        # token exploding while draft/verify stay flat
        K = spec_decoder.draft_tokens
        s_pos = np.full(
            engine.batch_slots, max(0, engine.max_seq - 2 - K), np.int32
        )
        s_tokens = np.ones(engine.batch_slots, np.int32)
        dlen = np.minimum(
            np.full(engine.batch_slots, K, np.int32),
            engine.max_seq - 1 - s_pos,
        ).astype(np.int32)
        keep = np.ones(engine.batch_slots, np.int32)
        for _ in range(warmup):
            spec_decoder.step(s_tokens, s_pos, dlen)
            spec_decoder.rollback(s_pos, keep)
        draft_s = verify_s = 0.0
        committed = 0
        for _ in range(iters):
            res = spec_decoder.step(s_tokens, s_pos, dlen)
            draft_s += res.draft_s
            verify_s += res.verify_s
            committed += int(res.accepted.sum()) + engine.batch_slots
            spec_decoder.rollback(s_pos, keep)
        t_draft = draft_s / iters
        t_verify = verify_s / iters
        tokens_per_verify = committed / (iters * engine.batch_slots)
        spec_total = t_draft + t_verify
        phases_ms["draft"] = round(t_draft * 1e3, 3)
        phases_ms["verify"] = round(t_verify * 1e3, 3)
        out["spec_step_ms"] = round(spec_total * 1e3, 3)
        out["drafter"] = spec_decoder.drafter_name
        out["draft_tokens"] = K
        out["tokens_per_verify"] = round(tokens_per_verify, 4)
        out["ms_per_committed_token"] = round(
            spec_total * 1e3 / max(tokens_per_verify, 1e-9), 3
        )
    return out


def attribute_regression(
    baseline: Dict[str, Any], candidate: Dict[str, Any]
) -> Dict[str, Any]:
    """Name the phase that explains a decode regression.

    Compares two :func:`decode_phase_breakdown` results; the hottest
    phase is the one whose per-phase time GREW the most from baseline to
    candidate, reported with its absolute delta and its share of the
    candidate's step time — the "where did the 82 ms go" answer
    QUANT_r10 could not give.

    Deltas are computed over the phases BOTH breakdowns measured: a
    phase present on only one side (e.g. comparing a pre-split
    ``attention_mlp_other`` baseline against the ``attention_kernel`` /
    ``mlp_other`` split) has no meaningful delta — zero-defaulting it
    would report the candidate phase's WHOLE time as growth.  One-sided
    phases are surfaced in ``unmatched_phases`` instead of silently
    skewing the attribution.
    """
    common = [n for n in candidate["phases_ms"] if n in baseline["phases_ms"]]
    unmatched = sorted(
        set(candidate["phases_ms"]) ^ set(baseline["phases_ms"])
    )
    deltas = {
        name: round(
            candidate["phases_ms"][name] - baseline["phases_ms"][name], 3
        )
        for name in common
    }
    total = max(candidate["decode_step_ms"], 1e-9)
    out = {
        "decode_step_ms": {
            "baseline": baseline["decode_step_ms"],
            "candidate": candidate["decode_step_ms"],
        },
        "regression_ms": round(
            candidate["decode_step_ms"] - baseline["decode_step_ms"], 3
        ),
        "phase_delta_ms": deltas,
    }
    if unmatched:
        out["unmatched_phases"] = unmatched
    if deltas:
        hottest = max(deltas, key=lambda k: deltas[k])
        out["hottest_phase"] = hottest
        out["hottest_phase_delta_ms"] = deltas[hottest]
        out["hottest_phase_share_of_step_time"] = round(
            candidate["phases_ms"][hottest] / total, 4
        )
    else:
        out["hottest_phase"] = "decode_step"
        out["hottest_phase_delta_ms"] = out["regression_ms"]
        out["hottest_phase_share_of_step_time"] = 1.0
    return out
