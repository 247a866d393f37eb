"""Attribution layer (ISSUE 15): program cost registry, HBM ledger,
forecast-gated admission, straggler timing, recorder dump context, and
the hardened perf-history reader.

The owner-totals-vs-live-bytes reconciliation gates run in a SUBPROCESS
(``ddlt obs attrib --check``): ``jax.live_arrays()`` in the shared
pytest process carries every other test's leftovers, so the residual is
only meaningful in a process the check owns end to end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.obs import attrib as attrib_mod
from distributeddeeplearning_tpu.obs import ledger as ledger_mod
from distributeddeeplearning_tpu.obs.attrib import (
    ProgramCostRegistry,
    TrackedProgram,
    compute_collective_split,
    step_phase_stats,
    straggler_report,
)
from distributeddeeplearning_tpu.obs.ledger import HBMLedger
from distributeddeeplearning_tpu.obs.recorder import (
    FlightRecorder,
    register_dump_context,
)
from distributeddeeplearning_tpu.utils.roofline import program_roofline


# --- program cost registry -------------------------------------------------


class TestTrackedProgram:
    def test_records_signature_per_compile_and_resolves_cost(self):
        reg = ProgramCostRegistry()
        fn = reg.track("t.matmul", jax.jit(lambda a, b: a @ b))
        x = jnp.ones((16, 16))
        fn(x, x)
        fn(x, x)  # same shape: no new compile, no new signature
        assert len(fn.signatures) == 1
        y = jnp.ones((32, 32))
        fn(y, y)  # new shape -> new compile -> second signature
        assert len(fn.signatures) == 2
        costs = fn.collect()
        assert len(costs) == 2
        assert all(c.available for c in costs)
        # 2*n^3 model flops per matmul: the two signatures differ 8x
        flops = sorted(c.flops for c in costs)
        assert flops[0] > 0 and flops[1] == pytest.approx(
            flops[0] * 8, rel=0.01
        )

    def test_memory_analysis_on_demand(self):
        reg = ProgramCostRegistry()
        fn = reg.track("t.add", jax.jit(lambda a: a + 1.0))
        fn(jnp.ones((64,)))
        (cost,) = fn.collect(memory=True)
        assert cost.argument_bytes == 64 * 4
        assert cost.output_bytes == 64 * 4
        assert cost.temp_bytes is not None

    def test_donated_args_record_fine(self):
        # signatures abstract AFTER the call — donated (deleted) buffers
        # must still yield their aval metadata
        reg = ProgramCostRegistry()
        fn = reg.track(
            "t.donate",
            jax.jit(lambda c: {"k": c["k"] * 2}, donate_argnums=(0,)),
        )
        fn({"k": jnp.ones((8, 8))})
        assert len(fn.signatures) == 1
        (cost,) = fn.collect()
        assert cost.available

    def test_static_args_survive_relowering(self):
        reg = ProgramCostRegistry()
        fn = reg.track("t.static", jax.jit(
            lambda a, flag: a * 2 if flag else a, static_argnums=(1,)
        ))
        fn(jnp.ones((8,)), True)
        (cost,) = fn.collect()
        assert cost.available and cost.error is None

    def test_attribute_forwarding(self):
        # the program audit calls .trace/.lower and the lint pins
        # _cache_size on the wrapped jit — the wrapper must be
        # transparent to all of them
        reg = ProgramCostRegistry()
        inner = jax.jit(lambda a: a + 1)
        fn = reg.track("t.fwd", inner)
        assert fn._cache_size() == 0
        lowered = fn.lower(jax.ShapeDtypeStruct((4,), jnp.float32))
        assert "stablehlo" in lowered.as_text() or lowered is not None
        fn(jnp.ones((4,)))
        assert fn._cache_size() == 1

    def test_registry_holds_programs_weakly(self):
        import gc

        reg = ProgramCostRegistry()
        fn = reg.track("t.weak", jax.jit(lambda a: a))
        assert reg.names() == ["t.weak"]
        del fn
        gc.collect()
        assert reg.names() == []

    def test_collect_skips_never_compiled_programs(self):
        reg = ProgramCostRegistry()
        reg.track("t.nevercalled", jax.jit(lambda a: a))
        assert reg.collect() == {}

    def test_dump_table_never_lowers(self):
        # before any collect, the crash-dump attachment is the bare
        # signature inventory (mid-failure it must not trace anything)
        reg = ProgramCostRegistry()
        fn = reg.track("t.dump", jax.jit(lambda a: a * 3))
        fn(jnp.ones((4,)))
        table = reg.dump_table()
        assert table and table[0]["name"] == "t.dump"
        assert table[0]["available"] is False
        reg.collect()
        assert reg.dump_table()[0]["available"] is True


# --- HBM ledger ------------------------------------------------------------


class TestHBMLedger:
    def test_owner_totals_and_dedup(self):
        led = HBMLedger()
        a = jnp.ones((128,))  # 512 B
        b = jnp.ones((64,))   # 256 B
        holder = {"a": a, "b": b}
        led.register("one", holder, lambda h: {"a": h["a"]})
        led.register("two", holder, lambda h: {"a": h["a"], "b": h["b"]})
        snap = led.snapshot(reconcile=False)
        # leaf `a` is claimed by owner "one" first; owner "two" gets
        # only the unclaimed `b` — no byte counts twice
        assert snap["owners"]["one"]["bytes"] == 512
        assert snap["owners"]["two"]["bytes"] == 256
        assert snap["total_bytes"] == 768
        assert snap["per_device_bytes"]
        assert sum(snap["per_device_bytes"].values()) == 768

    def test_committed_overrides_and_forecast(self):
        led = HBMLedger()
        pool = {"k": jnp.ones((256,))}  # 1024 B reserved
        state = {"committed": 128}
        led.register(
            "pool", state, lambda s: pool,
            committed=lambda s: s["committed"],
        )
        snap = led.snapshot(reconcile=False)
        assert snap["owners"]["pool"]["bytes"] == 1024
        assert snap["owners"]["pool"]["committed_bytes"] == 128
        # no capacity: always admit, cheap path
        assert led.admit_ok(10**12)
        f = led.forecast(100)
        assert f["admit"] and f["capacity_bytes"] is None
        led.set_capacity(300)
        assert led.forecast(100)["admit"] is True   # 128+100 <= 300
        assert led.forecast(200)["admit"] is False  # 128+200 > 300
        state["committed"] = 300
        assert led.admit_ok(1) is False  # live committed read each time

    def test_weakref_target_drop(self):
        import gc

        led = HBMLedger()

        class Holder:
            pass

        h = Holder()
        h.tree = {"x": jnp.ones((32,))}
        led.register("gone", h, lambda o: o.tree)
        assert led.snapshot(reconcile=False)["owners"]["gone"]["bytes"] > 0
        del h
        gc.collect()
        assert "gone" not in led.snapshot(reconcile=False)["owners"]

    def test_watermarks_are_monotone(self):
        led = HBMLedger()
        holder = {"t": jnp.ones((256,))}
        led.register("w", holder, lambda h: dict(h))
        led.snapshot(reconcile=False)
        assert led.watermarks["w"] == 1024
        holder.clear()
        snap = led.snapshot(reconcile=False)
        assert snap["owners"]["w"]["bytes"] == 0
        assert snap["owners"]["w"]["peak_bytes"] == 1024  # held

    def test_export_gauges(self):
        from distributeddeeplearning_tpu.obs.registry import MetricsRegistry

        led = HBMLedger()
        led.register("g", {"t": jnp.ones((64,))}, lambda h: dict(h))
        reg = MetricsRegistry()
        led.export_gauges(reg)
        snap = reg.snapshot()
        assert snap["gauges"]["hbm.g.bytes"] == 256.0
        assert snap["gauges"]["hbm.total_bytes"] == 256.0
        assert snap["gauges"]["hbm.g.peak_bytes"] == 256.0

    def test_accounting_never_inflates_live_arrays(self):
        # the 50%-residual bug class: walking shards (or even
        # hasattr(addressable_shards)) registers tracked per-shard
        # views, inflating the live_arrays() total the ledger
        # reconciles against.  The walk must be metadata-only.
        led = HBMLedger()
        holder = {"x": jnp.ones((128, 128))}
        led.register("inflate", holder, lambda h: dict(h))
        import gc

        gc.collect()
        before = len(jax.live_arrays())
        for _ in range(3):
            led.snapshot(reconcile=True)
        gc.collect()
        assert len(jax.live_arrays()) == before


# --- forecast-gated admission (the acceptance-criterion test) --------------


@pytest.mark.timeout(240)
class TestForecastAdmission:
    def test_headroom_zero_backpressures_never_ooms(self):
        """Drive predicted headroom to ~one request: every request still
        completes (backpressure queues, never a mid-decode OOM path),
        and committed bytes never exceed the configured capacity."""
        from distributeddeeplearning_tpu.models.pipelined_transformer import (
            init_params,
        )
        from distributeddeeplearning_tpu.serve.engine import (
            PagedInferenceEngine,
            _register_engine_owners,
        )
        from distributeddeeplearning_tpu.serve.scheduler import (
            ContinuousBatchingScheduler,
            synthetic_requests,
        )

        params = init_params(
            jax.random.key(0), max_len=48, num_layers=2, d_model=32,
            num_heads=4, d_ff=64, vocab_size=211,
        )
        engine = PagedInferenceEngine(
            params, num_heads=4, batch_slots=4, max_seq=48,
            page_size=8, prefill_chunk=8,
        )
        led = HBMLedger()
        _register_engine_owners(engine, led)
        reqs = synthetic_requests(
            5, vocab_size=211, max_prompt=16,
            rng=np.random.default_rng(0),
        )
        new_tokens = 4
        worst = max(
            engine.admit_bytes(len(r.prompt), new_tokens) for r in reqs
        )
        capacity = led.committed_bytes() + worst + engine._page_bytes
        led.set_capacity(capacity)
        max_in_use = 0

        def on_step(_step):
            nonlocal max_in_use
            max_in_use = max(max_in_use, engine.allocator.pages_in_use)

        results, report = ContinuousBatchingScheduler(
            engine, max_new_tokens=new_tokens, hbm_ledger=led,
        ).run(list(reqs), on_step=on_step)
        assert report.errors == 0
        assert len(results) == len(reqs)
        assert all(r.finish_reason in ("eos", "length") for r in results)
        # the forecast held: committed demand never exceeded capacity
        assert 0 < led.peak_committed_bytes <= capacity
        # and the pool genuinely serialized: free slots/pages existed
        # for more concurrency than the ledger allowed
        assert max_in_use * engine._page_bytes <= worst + engine._page_bytes

    def test_no_capacity_is_a_noop(self):
        led = HBMLedger()
        assert led.admit_ok(10**15)


# --- recorder dump context -------------------------------------------------


class TestDumpContext:
    def test_dump_carries_ledger_and_program_costs(self):
        rec = FlightRecorder(capacity=16)
        rec.record_event("warmup")
        payload = rec.dump("unit_test")
        # obs.ledger / obs.attrib registered their providers at import
        assert "hbm_ledger" in payload
        assert isinstance(payload["hbm_ledger"], dict)
        assert "owners" in payload["hbm_ledger"]
        assert "program_costs" in payload
        assert isinstance(payload["program_costs"], list)

    def test_broken_provider_never_breaks_dump(self):
        def boom():
            raise RuntimeError("mid-crash provider")

        register_dump_context("broken_ctx", boom)
        try:
            payload = FlightRecorder(capacity=4).dump("unit_test")
            assert payload["broken_ctx"] is None
        finally:
            register_dump_context("broken_ctx", None)

    def test_explicit_context_wins_over_provider(self):
        register_dump_context("clash", lambda: "from-provider")
        try:
            payload = FlightRecorder(capacity=4).dump(
                "unit_test", clash="explicit"
            )
            assert payload["clash"] == "explicit"
        finally:
            register_dump_context("clash", None)


# --- straggler / clock-skew ------------------------------------------------


def _make_shard(process_name, pid, spans, epoch_shift_s=0.0):
    """A synthetic Chrome-trace shard: ``spans`` = [(name, ts_us,
    dur_us)], with the wall epoch optionally skewed."""
    import time

    return {
        "traceEvents": [
            {
                "ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": process_name},
            },
        ] + [
            {
                "ph": "X", "name": name, "cat": "host", "pid": pid,
                "tid": 1, "ts": ts, "dur": dur, "args": {},
            }
            for name, ts, dur in spans
        ],
        "metadata": {
            "tracer_epoch_unix_s": time.time() + epoch_shift_s,
            "host_pids": [pid],
            "process_name": process_name,
        },
    }


class TestStragglerTiming:
    def test_slowest_host_attribution(self):
        fast = _make_shard("host-a", 11, [
            ("train/step", 0.0, 1000.0),
            ("train/step", 2000.0, 1200.0),
        ])
        slow = _make_shard("host-b", 22, [
            ("train/step", 0.0, 3000.0),
            ("train/step", 4000.0, 3400.0),
        ])
        rep = straggler_report([fast, slow], phases=("train/step",))
        phase = rep["phases"]["train/step"]
        assert phase["slowest_host"] == "host-b"
        assert phase["fastest_host"] == "host-a"
        assert phase["skew_pct"] == pytest.approx(
            (3200.0 - 1100.0) / 1100.0 * 100.0, abs=0.01
        )
        assert rep["negative_spans"] == 0

    def test_wall_clock_skew_cannot_corrupt_durations_or_stats(self):
        # the satellite pin: durations are single-clock measurements, so
        # an arbitrary wall-clock offset between hosts changes NOTHING
        # in the per-host table and can never make a duration negative
        spans_a = [("serve/decode_step", 100.0, 500.0)]
        spans_b = [("serve/decode_step", 100.0, 900.0)]
        plain = straggler_report(
            [_make_shard("a", 1, spans_a), _make_shard("b", 2, spans_b)],
            phases=("serve/decode_step",),
        )
        skewed = straggler_report(
            [
                _make_shard("a", 1, spans_a, epoch_shift_s=-3600.0),
                _make_shard("b", 2, spans_b, epoch_shift_s=+7200.0),
            ],
            phases=("serve/decode_step",),
        )
        assert plain["phases"] == skewed["phases"]
        assert skewed["negative_spans"] == 0

    def test_phase_filter(self):
        shard = _make_shard("a", 1, [
            ("train/step", 0.0, 10.0),
            ("some/other_span", 0.0, 10.0),
        ])
        stats = step_phase_stats(
            shard["traceEvents"], phases=("train/step",)
        )
        assert set(stats) == {"train/step"}

    def test_colliding_pids_stay_separate_hosts(self):
        # two containerized workers on different machines can BOTH be
        # pid 1 — the exact collision merge_fleet_trace remaps; the
        # straggler table must keep them separate hosts, not average
        # them into one fictional row that hides the real straggler
        fast = _make_shard("host-a", 1, [("train/step", 0.0, 1000.0)])
        slow = _make_shard("host-b", 1, [("train/step", 0.0, 3000.0)])
        report = straggler_report([fast, slow])
        assert report["hosts"] == ["host-a", "host-b"]
        phase = report["phases"]["train/step"]
        assert phase["slowest_host"] == "host-b"
        assert phase["fastest_host"] == "host-a"
        assert phase["skew_pct"] == 200.0

    def test_raw_event_list_and_bare_dict_shards(self):
        # a shard may be a raw Chrome-trace event LIST (the JSON-array
        # flavor of the format) or a dict without traceEvents — neither
        # may crash the report
        raw = _make_shard("host-c", 7, [("train/step", 0.0, 500.0)])
        report = straggler_report([raw["traceEvents"]])
        assert report["hosts"] == ["host-c"]
        assert "train/step" in report["phases"]
        assert straggler_report([{"displayTimeUnit": "ms"}])["hosts"] == []


class TestMergeUnderSkew:
    """Cross-process trace-span merging under clock skew (obs/fleet.py
    + obs/trace.py): offsets shift timestamps only — one host's span
    ORDER survives, durations stay non-negative, and a handshake offset
    restores cross-host order that raw skewed walls would scramble."""

    def _merge(self, router, shards, **kw):
        from distributeddeeplearning_tpu.obs.fleet import merge_fleet_trace

        return merge_fleet_trace(router, shards, **kw)

    def test_skew_preserves_per_host_order_and_durations(self):
        import time

        router = {
            "traceEvents": [],
            "metadata": {
                "tracer_epoch_unix_s": time.time(), "host_pids": [1],
            },
        }
        # worker wall clock 90 s ahead; its own spans are strictly
        # ordered A -> B on its clock
        shard = _make_shard("worker", 33, [
            ("serve/decode_step", 1000.0, 400.0),
            ("serve/decode_step", 2000.0, 450.0),
        ], epoch_shift_s=90.0)
        merged = self._merge(router, [shard])
        spans = [
            e for e in merged["traceEvents"]
            if e.get("ph") == "X" and e.get("pid") == 33
        ]
        assert len(spans) == 2
        assert spans[0]["ts"] < spans[1]["ts"]  # order survives
        assert spans[0]["ts"] + spans[0]["dur"] <= spans[1]["ts"]
        assert all(e["dur"] >= 0 for e in spans)
        # the epoch offset landed them ~90 s later on the router clock
        assert spans[0]["ts"] == pytest.approx(90e6 + 1000.0, abs=5e5)

    def test_handshake_offset_restores_cross_host_order(self):
        import time

        epoch = time.time()
        router = {
            "traceEvents": [
                {"ph": "X", "name": "router/admit", "pid": 1, "tid": 1,
                 "ts": 0.0, "dur": 100.0, "args": {}},
            ],
            "metadata": {"tracer_epoch_unix_s": epoch, "host_pids": [1]},
        }
        # worker span REALLY happened 5 ms after the router admit, but
        # its wall epoch claims an hour earlier — epoch alignment alone
        # would sort it before the admit; the measured handshake offset
        # (+5000 us onto the router clock) must win
        shard = _make_shard("worker", 44, [
            ("serve/prefill_chunk", 0.0, 2000.0),
        ], epoch_shift_s=-3600.0)
        merged = self._merge(router, [shard], offsets_us={44: 5000.0})
        span = next(
            e for e in merged["traceEvents"]
            if e.get("ph") == "X" and e.get("name") == "serve/prefill_chunk"
        )
        assert span["ts"] == pytest.approx(5000.0)
        assert span["ts"] > 0.0  # lands after the admit span's start
        assert merged["metadata"]["shards"][0]["offset_source"] == (
            "handshake"
        )
        assert span["dur"] == 2000.0  # never rescaled by alignment


# --- roofline / split math -------------------------------------------------


class TestRooflineMath:
    def test_program_roofline_with_peaks(self):
        out = program_roofline(
            1e12, 1e9, 0.01, peak_tflops=100.0, peak_hbm_gbps=1000.0,
        )
        assert out["roofline_available"]
        assert out["achieved_tflops"] == pytest.approx(100.0)
        assert out["pct_of_compute_roofline"] == pytest.approx(1.0)
        # compute time 0.01 s vs bandwidth time 0.000001 s
        assert out["bound"] == "compute"
        assert out["roofline_s"] == pytest.approx(0.01)
        assert out["efficiency"] == pytest.approx(1.0)

    def test_program_roofline_without_peaks(self):
        out = program_roofline(1e9, 1e9, 0.5)
        assert out["roofline_available"] is False
        assert "pct_of_compute_roofline" not in out
        assert out["achieved_gbps"] == pytest.approx(2.0)

    def test_compute_collective_split(self):
        out = compute_collective_split(
            1e12, 1e9, peak_flops=1e12, interconnect_gbps=1.0,
            measured_step_s=4.0,
        )
        assert out["estimated"] is True
        assert out["compute_s"] == pytest.approx(1.0)
        assert out["collective_s"] == pytest.approx(1.0)
        assert out["compute_fraction"] == pytest.approx(0.5)
        assert out["unexplained_s"] == pytest.approx(3.0)

    def test_reference_peaks_never_mix_sources(self):
        # the "device" label requires BOTH ceilings from the real chip's
        # datasheet tables — a chip with a known compute peak must not be
        # paired with another chip's memory bandwidth (a v5p roofline
        # built on v5e's 819 GB/s would flip compute-bound programs to
        # "hbm-bandwidth"); on CPU both lookups miss and the v5e
        # nominals are returned explicitly labeled as reference numbers;
        # an accelerator missing from the tables raises instead of
        # borrowing them
        from types import SimpleNamespace
        from unittest import mock

        from distributeddeeplearning_tpu.obs.attrib import reference_peaks
        from distributeddeeplearning_tpu.utils.hardware import (
            peak_bf16_flops,
            peak_hbm_gbps,
        )

        tflops, gbps, source = reference_peaks()
        assert source == "v5e-nominal-reference"  # CPU backend
        assert (tflops, gbps) == (197.0, 819.0)
        v5p = SimpleNamespace(device_kind="TPU v5p")
        assert peak_hbm_gbps(v5p) == 2765.0
        assert peak_bf16_flops(v5p) == 459e12
        assert peak_hbm_gbps(SimpleNamespace(device_kind="cpu")) is None
        unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99")
        with mock.patch("jax.devices", return_value=[unknown]):
            with pytest.raises(ValueError, match="no published peaks"):
                reference_peaks()
        known = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        with mock.patch("jax.devices", return_value=[known]):
            assert reference_peaks() == (197.0, 819.0, "device")


# --- hardened history reader ------------------------------------------------


class TestHistoryHardening:
    def _write(self, path, payload):
        with open(path, "w") as f:
            if isinstance(payload, str):
                f.write(payload)
            else:
                json.dump(payload, f)

    def _mk(self, tmp, r02_value=100.0):
        self._write(tmp / "PERF_r01.json", {
            "metric": "tok", "value": 100.0, "unit": "tok/s",
            "decode_tokens_per_sec": 100.0,
        })
        self._write(tmp / "PERF_r02.json", {
            "metric": "tok", "value": r02_value, "unit": "tok/s",
            "decode_tokens_per_sec": r02_value,
        })
        # a partially-written artifact (writer died mid-dump)
        self._write(tmp / "PERF_r03.json", '{"metric": "tok", "val')

    def test_truncated_artifact_skipped_with_warning_gate_green(self, tmp_path):
        from distributeddeeplearning_tpu.obs.history import run_history

        self._mk(tmp_path)
        rc, out = run_history(str(tmp_path), gate=True)
        assert rc == 0, out
        assert "skipped malformed artifact" in out
        assert "PERF_r03.json" in out

    def test_gate_still_red_on_genuine_regression(self, tmp_path):
        from distributeddeeplearning_tpu.obs.history import run_history

        self._mk(tmp_path, r02_value=50.0)  # -50% decode throughput
        rc, out = run_history(str(tmp_path), gate=True)
        assert rc == 1
        assert "skipped malformed artifact" in out
        assert "REGRESSION" in out

    def test_empty_container_treated_as_malformed(self, tmp_path):
        from distributeddeeplearning_tpu.obs.history import run_history

        self._mk(tmp_path)
        self._write(tmp_path / "PERF_r04.json", "{}")
        rc, out = run_history(str(tmp_path), gate=True)
        assert rc == 0, out
        assert "PERF_r04.json" in out

    def test_new_tolerances_registered(self):
        from distributeddeeplearning_tpu.obs.history import TOLERANCES

        assert "unaccounted_hbm_pct" in TOLERANCES
        assert TOLERANCES["unaccounted_hbm_pct"].higher_is_better is False
        assert "programs_covered" in TOLERANCES
        assert TOLERANCES["programs_covered"].higher_is_better is True

    def test_programs_covered_shrink_gates_red(self, tmp_path):
        from distributeddeeplearning_tpu.obs.history import run_history

        self._write(tmp_path / "A_r01.json", {
            "metric": "m", "value": 1.0, "unit": "u",
            "programs_covered": 10,
        })
        self._write(tmp_path / "A_r02.json", {
            "metric": "m", "value": 1.0, "unit": "u",
            "programs_covered": 9,
        })
        rc, out = run_history(str(tmp_path), gate=True)
        assert rc == 1
        assert "programs_covered" in out


# --- artifact schema -------------------------------------------------------


class TestAttribSchema:
    def _load_committed(self):
        path = os.path.join(os.path.dirname(__file__), "..",
                            "ATTRIB_r18.json")
        with open(path) as f:
            return json.load(f)

    def test_committed_artifact_validates(self):
        from distributeddeeplearning_tpu.obs.schema import (
            validate_attrib_payload,
        )

        validate_attrib_payload(self._load_committed())

    def test_residual_over_limit_rejected(self):
        from distributeddeeplearning_tpu.obs.schema import (
            SchemaError,
            validate_attrib_payload,
        )

        bad = self._load_committed()
        bad["unaccounted_hbm_pct"] = 40.0
        with pytest.raises(SchemaError, match="residual gate"):
            validate_attrib_payload(bad)

    def test_negative_spans_rejected(self):
        from distributeddeeplearning_tpu.obs.schema import (
            SchemaError,
            validate_attrib_payload,
        )

        bad = self._load_committed()
        bad["straggler"]["negative_spans"] = 2
        with pytest.raises(SchemaError, match="negative"):
            validate_attrib_payload(bad)

    def test_missing_gate_rejected(self):
        from distributeddeeplearning_tpu.obs.schema import (
            SchemaError,
            validate_attrib_payload,
        )

        bad = self._load_committed()
        del bad["gates"]["forecast_backpressure"]
        with pytest.raises(SchemaError, match="forecast_backpressure"):
            validate_attrib_payload(bad)


# --- fleet watermark lift --------------------------------------------------


class TestFleetWatermarks:
    def test_hbm_gauges_lifted_per_replica(self):
        from distributeddeeplearning_tpu.serve.fleet import _hbm_watermarks

        states = [
            {
                "replica_id": 0, "pid": 100,
                "gauges": {
                    "hbm.kv_pages.bytes": {"value": 4096.0},
                    "hbm.kv_pages.peak_bytes": {"value": 8192.0},
                    "serve.tokens_per_sec": {"value": 12.0},
                },
            },
            {"replica_id": 1, "pid": 101, "gauges": {}},
        ]
        wm = _hbm_watermarks(states)
        assert wm == {
            "replica0-100": {
                "hbm.kv_pages.bytes": 4096.0,
                "hbm.kv_pages.peak_bytes": 8192.0,
            },
        }


# --- trainer registration --------------------------------------------------


class TestTrainerLedgerOwners:
    def test_register_hbm_owners_reads_live_state(self):
        from distributeddeeplearning_tpu.train.loop import Trainer

        led = ledger_mod.set_ledger(HBMLedger())
        try:
            t = Trainer.__new__(Trainer)

            class FakeState:
                params = {"w": jnp.ones((64,))}
                opt_state = {"m": jnp.ones((64,))}
                batch_stats = {}

            t._obs_state = FakeState()
            t._register_hbm_owners()
            t._register_hbm_owners()  # idempotent
            snap = led.snapshot(reconcile=False)
            assert snap["owners"]["params"]["bytes"] == 256
            assert snap["owners"]["opt_state"]["bytes"] == 256
            # keep `t` alive through the snapshot (weakref provider)
            assert t._hbm_registered
        finally:
            ledger_mod.set_ledger(HBMLedger())


# --- the hermetic gate (subprocess: owns its own live_arrays) ---------------


@pytest.mark.timeout(280)
def test_obs_attrib_check_green_in_subprocess():
    """``ddlt obs attrib --check`` — the make obs-gate half: every
    tracked program resolves a cost row on the CPU backend, ledger
    owner totals reconcile against the process's live device bytes
    within 1%, and the unaccounted-HBM residual stays under 5%."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DDLT_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "distributeddeeplearning_tpu.cli.main",
         "obs", "attrib", "--check"],
        env=env, text=True, capture_output=True, timeout=260,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["gates"]["programs_covered"] is True
    assert verdict["gates"]["owner_totals_match_live"] is True
    assert verdict["gates"]["residual_under_limit"] is True
    assert verdict["unaccounted_hbm_pct"] <= 5.0
