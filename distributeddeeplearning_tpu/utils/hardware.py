"""Device peak-FLOPs lookup for MFU accounting.

The reference never reports utilization — only img/sec
(``pytorch_synthetic_benchmark.py:119-126``).  On TPU, img/sec alone hides
whether the MXU is actually busy, so the benchmark harness divides sustained
model FLOP/s by the chip's peak bf16 FLOP/s (MFU, as defined in the PaLM
paper's appendix).  Peaks are the public per-chip bf16/fp16 dense figures
from the TPU and GPU datasheets.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

import jax

# device_kind substring (lowercased) -> peak dense bf16/fp16 FLOP/s per chip
_PEAK_BF16_FLOPS = [
    ("v6e", 918e12),  # Trillium
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),  # device_kind "TPU v5 lite" (v5e)
    ("v5litepod", 197e12),
    ("v5", 459e12),  # bare "TPU v5" = v5p
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("h100", 989e12),
    ("a100", 312e12),
    ("v100", 125e12),
]


# device_kind substring (lowercased) -> peak HBM bandwidth GB/s per chip,
# same datasheet sources (and the same substring keys) as the FLOPs table
_PEAK_HBM_GBPS = [
    ("v6e", 1640.0),  # Trillium
    ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v5litepod", 819.0),
    ("v5", 2765.0),  # bare "TPU v5" = v5p
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
    ("h100", 3350.0),
    ("a100", 1555.0),  # 40GB figure; the 80GB part reaches 2039
    ("v100", 900.0),
]


def peak_bf16_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak dense bf16 FLOP/s for ``device`` (default: first visible device).

    Returns None — NEVER raises — when the device kind is unrecognized
    (the CPU backend used by the virtual test mesh reports kinds like
    ``"cpu"``) or when the backend cannot even report a kind: callers
    must then omit MFU rather than report a made-up number.  An
    exception here would turn "unknown chip" into a crashed benchmark,
    which is strictly worse than a missing utilization column.
    """
    try:
        if device is None:
            device = jax.devices()[0]
        kind = device.device_kind.lower()
    except Exception:
        return None  # no devices / kind-less backend: MFU omitted
    for key, peak in _PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    return None


def peak_hbm_gbps(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak HBM bandwidth in GB/s for ``device`` (default: first visible
    device).  Same contract as :func:`peak_bf16_flops`: None — never an
    exception — for unrecognized or kind-less devices, so callers fall
    back to labeled reference numbers instead of pairing a real compute
    peak with another chip's memory ceiling."""
    try:
        if device is None:
            device = jax.devices()[0]
        kind = device.device_kind.lower()
    except Exception:
        return None
    for key, peak in _PEAK_HBM_GBPS:
        if key in kind:
            return peak
    return None


def mfu(
    flops_per_step: float,
    steps: int,
    wall_s: float,
    *,
    device: Optional[jax.Device] = None,
    n_chips: Optional[int] = None,
) -> Optional[float]:
    """Model FLOPs Utilization, as defined in the PaLM paper's appendix:
    the model's *observed* FLOP throughput as a fraction of the
    hardware's peak.  The formula actually computed here::

        MFU = (flops_per_step × steps / wall_s) / (peak_bf16_flops × n_chips)

    where ``flops_per_step`` is the MODEL FLOPs of one train step (XLA's
    own cost model via :func:`step_flops`, or an analytic count — NOT
    hardware FLOPs: rematerialization re-executes work without raising
    MFU), ``wall_s`` is the whole window being scored (a run-level MFU
    divides by total wall, overheads included — that is the point), and
    ``n_chips`` defaults to every visible device.

    Returns None when the chip's peak is unknown (CPU / virtual test
    mesh — :func:`peak_bf16_flops` returns None there) or the inputs are
    degenerate; callers omit the MFU column rather than fabricate one.
    """
    if flops_per_step <= 0 or steps <= 0 or wall_s <= 0:
        return None
    peak = peak_bf16_flops(device)
    if peak is None:
        return None
    if n_chips is None:
        try:
            n_chips = jax.device_count()
        except Exception:
            return None
    if n_chips <= 0:
        return None
    return (flops_per_step * steps / wall_s) / (peak * n_chips)


#: Where the persistent compile cache lives when nothing outside places
#: it: one fixed path inside the checkout (the path is part of the cache
#: key's environment — a directory that moves between runs never hits),
#: derived from the package's location and ignored by git.
DEFAULT_COMPILATION_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_cache"
)


def enable_compilation_cache(min_compile_time_secs: float = 1.0) -> str:
    """Turn on the persistent XLA compilation cache for this process —
    every entry point (``ddlt serve``/``train``, fleet workers, bench,
    ``chip_smoke.py``) calls this once, so repeated invocations of the
    same program load instead of recompiling.  Returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside (JAX reads the variable itself) and no directory is set in
    code; otherwise it goes to :data:`DEFAULT_COMPILATION_CACHE_DIR`.
    Programs that compiled faster than ``min_compile_time_secs`` are not
    stored."""
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs
    )
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.makedirs(DEFAULT_COMPILATION_CACHE_DIR, exist_ok=True)
    jax.config.update(
        "jax_compilation_cache_dir", DEFAULT_COMPILATION_CACHE_DIR
    )
    return DEFAULT_COMPILATION_CACHE_DIR


def device_summary() -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` of this process's backend, as
    JAX reports it — what every report and artifact names its device by.
    Initialises the backend (and so takes the chip)."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


_PROBE_SNIPPET = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))"
)


def probe_devices(timeout_s: float = 300.0) -> Dict[str, Any]:
    """:func:`device_summary` taken by a throwaway child process, for a
    parent that must stay off the backend: a chip belongs to one process
    at a time, so a router or bench parent that is about to start workers
    asks a child that has exited (and released the chip) by the time
    this returns.  Raises when the child cannot initialise a backend."""
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE_SNIPPET],
        capture_output=True, text=True, timeout=timeout_s,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            "device probe failed (no usable JAX backend?): "
            + proc.stderr.strip()[-500:]
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def step_flops(compiled) -> Optional[float]:
    """Total FLOPs of one execution of an XLA program.

    Reads XLA's own cost model via ``cost_analysis()`` — the same count
    the profiler uses.  Accepts a ``Compiled`` (post-optimization: tracks
    remat/fusion decisions) or a ``Lowered`` stage (pre-optimization
    model FLOPs — the MFU numerator, obtainable WITHOUT paying a second
    compile; the goodput ledger probes this form).
    """
    try:
        analysis = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not analysis:
        return None
    flops = analysis.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)
