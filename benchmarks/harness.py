"""What every cell shares: finding files by name, the chip check, the compile
counter, the profiler window, the device block and the result line."""
import importlib.util
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRIVERS = {"serve": "serve_driver", "train": "train_driver"}
_FAMILIES = {}  # file -> module: a family is loaded once in a process


def _json(*parts):
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return _json(ROOT, entry["file"])
    raise SystemExit(f"no configuration named {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    return _json(HERE, "traffic", name + ".json")


def load_limits(cell_name: str) -> dict:
    return _json(HERE, "limits", cell_name + ".json")


def metrics_of(manifest: dict, section: str, cell_name: str):
    """The metrics of `section` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def _module_at(path: str, prefix: str, name: str):
    """The module in the file at `path` (names may hold `.` and `-`)."""
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric_name: str):
    """A per-layer metric is a small reader of its own, found by name."""
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    return _module_at(path, "layer_metric_", metric_name).read


def load_family(cfg: dict, needs=()):
    """Everything that depends on the model's architecture sits behind one
    module, `families/<family>.py`, found by the name the configuration's file
    gives under `family` (the contract is `families/opt.py`'s docstring).
    `needs` are the names this cell's driver will ask it for: a family that
    lacks one stops here, before any device is touched."""
    name = cfg.get("family")
    if not name:
        raise SystemExit("the configuration's file names no `family`: it has "
                         "to, there is no default (see benchmarks/families/)")
    folder = os.path.join(HERE, "families")
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"family {name!r}: no file benchmarks/families/{name}.py")
    module = _FAMILIES.get(path)
    if module is None:
        if folder not in sys.path:
            sys.path.insert(0, folder)  # a family imports its own files by name
        module = _FAMILIES[path] = _module_at(path, "benchmark_family_", name)
    for need in needs:
        if not hasattr(module, need):
            raise SystemExit(
                f"family {name!r} (benchmarks/families/{name}.py) gives no "
                f"`{need}`, which this cell's driver needs")
    return module


def family_of(ctx):
    """The family of a reader's `ctx`. The drivers always put it there. A ctx
    made by hand before the seam existed carries none (the repo's
    `tests/test_trace_capture.py`, which a benchmark PR may not edit) and is
    read as `opt`, the one family there was; a configuration never is."""
    family = getattr(ctx, "family", None)
    return family if family is not None else load_family({"family": "opt"})


def require_chips(chips: int, rehearsal: bool):
    """The devices this cell runs on. Without as many TPU chips the command
    fails, unless it is an explicit rehearsal (which reports no metric)."""
    import jax

    devices = jax.devices()
    if rehearsal:
        if len(devices) < chips:
            raise SystemExit(f"rehearsal needs {chips} devices, found {len(devices)}")
        return devices[:chips]
    if devices[0].platform != "tpu":
        print(f"benchmarks/run.py: JAX found no accelerator "
              f"(platform {devices[0].platform!r})", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"benchmarks/run.py: the cell asks for {chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says): the program's own placing function."""
    from distributeddeeplearning_tpu.utils.hardware import enable_compilation_cache

    return enable_compilation_cache(min_compile_time_secs=0.0)


class CompileCounter:
    """Counts the programs JAX builds (compiled or loaded from the cache)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1


class TraceWindow:
    """Traces a few seconds in the middle of the window, driven by `tick`
    from the measuring loop's own thread."""

    def __init__(self, enabled: bool, start_s: float, stop_s: float):
        self.enabled = enabled
        self.start_s, self.stop_s = start_s, stop_s
        self.dir = os.path.join(ROOT, "chiprun_out", "trace", str(os.getpid()))
        self.state = "off" if not enabled else "waiting"
        self.t_started = self.t_stopped = None
        self._mark = None

    def tick(self, elapsed: float):
        if self.state == "waiting" and elapsed >= self.start_s:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._mark = jax.profiler.TraceAnnotation("bench/window")
            self._mark.__enter__()
            self.t_started = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.stop_s:
            self.stop()

    def stop(self):
        if self.state != "tracing":
            return
        import jax

        self.t_stopped = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def events(self):
        import shutil

        import trace_reduce

        if self.state != "done":
            return None
        events = trace_reduce.load_events(trace_reduce.find_xplane(self.dir))
        if os.environ.get("BENCH_KEEP_TRACE") != "1":
            shutil.rmtree(self.dir, ignore_errors=True)
        return events


def mark(name: str):
    import jax

    return jax.profiler.TraceAnnotation("bench/" + name)


def device_block(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def judge(checks: dict) -> bool:
    """`checks` maps a short name to {"value", "limit"}; a run is correct
    when every value is a number no greater than its limit (no less, where the
    entry says `at_least`). An entry with no limit is a reading, not judged."""
    ok = True
    for entry in checks.values():
        v, lim = entry["value"], entry["limit"]
        if lim is None:
            continue
        if v is None or v != v:
            ok = False
        elif entry.get("at_least"):
            ok = ok and v >= lim
        else:
            ok = ok and v <= lim
    return ok


def judge_stand_ins(result: dict, checks: dict, stand_ins: dict):
    """A stand-in (the lower-precision control, a planted fault) is put in the
    program's place: its numbers go under the program's own names, beside the
    cell's own limits, through the same `judge`. Each has to come out false."""
    if not stand_ins:
        return
    result["stand_ins"] = {}
    for label, numbers in stand_ins.items():
        stood = dict(checks)
        for name, value in numbers.items():
            stood[name] = dict(checks[name], value=value)
        result["stand_ins"][label] = {
            "correct": judge(stood),
            "checks": {name: stood[name] for name in numbers}}


def emit(result: dict):
    """Each number compared beside its limit on standard error, then the one
    result line, with the comparisons under a key of their own that comes
    last."""
    checks = result.pop("checks")
    for label, stood in result.get("stand_ins", {}).items():
        for name, entry in stood["checks"].items():
            print(f"stand-in {label} {name}: value {entry['value']!r} "
                  f"limit {entry['limit']!r}", file=sys.stderr)
        print(f"stand-in {label}: correct {stood['correct']}", file=sys.stderr)
    for name, entry in checks.items():
        print(f"check {name}: value {entry['value']!r} limit {entry['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def context(**kw):
    return types.SimpleNamespace(**kw)


def fill_metrics(result, manifest, cell, numbers, ctx, traced):
    """With --trace 0 the line carries the cell's end-to-end metrics; with
    --trace 1 its per-layer metrics, each from a reader of its own. A reader
    that finds nothing to read returns nothing and the metric is left out."""
    import trace_reduce

    if not traced:
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            if numbers.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {
                    "value": numbers[m["name"]], "unit": m["unit"]}
        return
    ctx.numbers = numbers
    ctx.trace_lo = ctx.trace_hi = None
    rehearsal = result["device"]["platform"] != "tpu"
    if rehearsal and ctx.events is not None and not ctx.events["devices"]:
        ctx.events = None  # a CPU trace has no device plane to reduce
    if ctx.events is not None:
        ctx.trace_lo, ctx.trace_hi = trace_reduce.window_of(ctx.events)
        lo, hi = ctx.trace_lo, ctx.trace_hi
        result["device"]["busy_s"] = trace_reduce.busy_seconds(ctx.events, lo, hi)
        result["device"]["window_s"] = hi - lo
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ctx.events, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(
                ctx.events, lo, hi, default_mark=ctx.enclosing_mark),
        }
    for m in metrics_of(manifest, "per_layer", cell["name"]):
        try:
            value = load_reader(m["name"])(ctx)
        except KeyError:
            if not rehearsal:
                raise
            value = None  # no peaks off the chip: a rehearsal reports no share
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}


def decoded_contexts_in_trace(ctx):
    """The live context of every token a decode step produced inside the
    traced window (every token after a request's first comes from one decode
    step and reads the request's whole context), from the harness's records."""
    if ctx.tracer.t_started is None:
        return []
    lo, hi = ctx.tracer.t_started - ctx.t0, ctx.tracer.t_stopped - ctx.t0
    return [len(item.prompt) + k
            for item in ctx.schedule
            for k, t in enumerate(ctx.token_times[item.uid]) if k and lo <= t <= hi]
