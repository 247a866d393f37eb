"""From the profiler's trace to numbers: the benchmark's own reduction.

Two stages, so that the second can be checked on a small recorded trace
(`benchmarks/tests/`): `load_events` turns an `.xplane.pb` into plain lists,
and the functions below turn those lists into busy time, kernel time, program
time and idle gaps.

Device planes are named `/device:TPU:<n>`. Their `XLA Ops` line holds one
event per executed HLO operation (a Pallas kernel appears under the name its
`pallas_call` was given), `XLA Modules` one event per executed program.
"""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "bench/"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


_HLO = re.compile(r"^%?(\S+) = (\(?)([a-z0-9]+\[[^\]]*\])?")


def short_name(name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO text: keep the op's name
    and, for a single result, its type and shape (`copy.12 f32[65,24,64,32,64]`)."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    if m.group(2) or not m.group(3):
        return m.group(1)
    return f"{m.group(1)} {m.group(3)}"


def load_events(path: str) -> dict:
    """{"devices": {n: {"ops": [(name, start_s, dur_s)], "modules": [...]}},
    "marks": [(name, start_s, dur_s)]} with times in seconds on the trace's
    clock and op names shortened by `short_name`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "marks": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    dev[key].append((short_name(e.name), e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_MARK):
                        out["marks"].append(
                            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def window_of(events: dict, mark: str = "bench/window"):
    """The traced window [start, end] in seconds: the harness's own mark, or,
    where the host plane lacks it, the span of the device events."""
    for name, start, dur in events["marks"]:
        if name == mark:
            return start, start + dur
    spans = [(s, s + d) for dev in events["devices"].values()
             for _, s, d in dev["ops"]]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(evs, lo, hi):
    for name, s, d in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(events: dict, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    per_dev = []
    for dev in events["devices"].values():
        merged = _union((a, b) for _, a, b in _clip(dev["ops"], lo, hi))
        per_dev.append(sum(b - a for a, b in merged))
    if not per_dev:
        raise ValueError("the trace holds no device plane")
    return sum(per_dev) / len(per_dev)


def idle_share_pct(events: dict, lo: float, hi: float) -> float:
    """1 - the union of the device-op intervals over the window, in percent."""
    return 100.0 * (1.0 - busy_seconds(events, lo, hi) / (hi - lo))


def op_seconds(events: dict, lo: float, hi: float, pattern: str, line="ops"):
    """(total seconds, calls) of the events whose name matches `pattern`,
    averaged over devices."""
    rx = re.compile(pattern)
    total, calls, n = 0.0, 0, 0
    for dev in events["devices"].values():
        n += 1
        for name, a, b in _clip(dev[line], lo, hi):
            if rx.search(name):
                total += b - a
                calls += 1
    if not n:
        return 0.0, 0
    return total / n, calls / n


def _self_times(spans):
    """(name, self seconds) of properly nested spans: a `while` holds the
    operations of its body, so its own time is what they leave over."""
    out, stack = [], []  # stack of [name, end, self]
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a + 1e-12:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    out.extend((name, own) for name, _, own in stack)
    return out


def top_ops(events: dict, lo: float, hi: float, k: int = 10):
    """The device operations that took most time of their own (a loop's time
    less its body's): [[name, seconds]], averaged over devices."""
    totals = {}
    n = max(len(events["devices"]), 1)
    for dev in events["devices"].values():
        for name, own in _self_times(_clip(dev["ops"], lo, hi)):
            totals[name] = totals.get(name, 0.0) + max(own, 0.0) / n
    return [[name, sec] for name, sec in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events: dict, lo: float, hi: float, k: int = 10,
              default_mark: str = "no harness mark"):
    """The idle time of device 0, by the programs on either side of each gap
    and the harness mark the gap's middle falls in: [[name, seconds]]. A mark
    entered before the trace began is not in the trace: `default_mark` names
    the one the whole traced window lies in."""
    if not events["devices"]:
        return []
    dev = events["devices"][min(events["devices"])]
    merged = _union((a, b) for _, a, b in _clip(dev["ops"], lo, hi))
    modules = sorted((a, b, name) for name, a, b in _clip(dev["modules"], lo, hi))
    starts = [a for a, _, _ in modules]
    by_end = sorted((b, name) for _, b, name in modules)
    ends = [b for b, _ in by_end]
    marks = [(s, s + d, name) for name, s, d in events["marks"]
             if name != "bench/window"]

    def module_at(t, before):
        """The last program that ended by `t`, or the first to start from it."""
        if before:
            i = bisect.bisect_right(ends, t + 1e-9)
            return by_end[i - 1][1] if i else None
        i = bisect.bisect_left(starts, t - 1e-9)
        return modules[i][2] if i < len(modules) else None

    def mark_at(t):
        inner = [(e - s, name) for s, e, name in marks if s <= t <= e]
        return min(inner)[1] if inner else default_mark

    totals = {}
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a <= 0:
            continue
        name = (f"{_short(module_at(a, True))} -> {_short(module_at(b, False))}"
                f" [{mark_at((a + b) / 2)}]")
        totals[name] = totals.get(name, 0.0) + (b - a)
    return [[name, sec] for name, sec in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def _short(name):
    return "window edge" if name is None else re.sub(r"\(\d+\)$", "", name)
