"""Virtual CPU pod: run a driver on N faked devices.

A virtual pod is the CPU platform asked for N host devices:
``JAX_PLATFORMS=cpu`` plus ``--xla_force_host_platform_device_count=N`` in
``XLA_FLAGS``, both in the environment before the backend initialises.
``tests/conftest.py`` sets them for pytest; a driver that may already have
touched the backend gets them by re-exec'ing itself in a fresh child
(:func:`reexec_with_virtual_pod`).  Nothing here ever moves a process that
was not asked onto the CPU: the device-count flag alone only sizes the
host platform and leaves an accelerator run on its accelerator.

Shared by ``__graft_entry__.dryrun_multichip`` and ``bench.py --devices``.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from typing import List, Optional

SENTINEL = "_DDLT_VIRTUAL_POD_REEXEC"
_COUNT_FLAG = "xla_force_host_platform_device_count"


def is_reexec_child() -> bool:
    return os.environ.get(SENTINEL) == "1"


def cpu_platform_pinned() -> bool:
    """True when the environment pins JAX to the CPU platform — the one
    case in which a process is known, without asking a backend, to hold
    no chip."""
    return (
        os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
        == "cpu"
    )


def is_virtual_pod() -> bool:
    """True when this run's devices are faked CPUs: the CPU platform is
    pinned AND a host device count was forced.  The ONE definition every
    artifact-emitting entry point (bench.py, ``ddlt serve``) records, so
    CPU numbers can never masquerade as hardware in one artifact while
    being flagged in another — and a stray ``XLA_FLAGS`` on a real chip
    never labels hardware numbers virtual."""
    return cpu_platform_pinned() and (
        _COUNT_FLAG in os.environ.get("XLA_FLAGS", "")
    )


def reexec_with_virtual_pod(
    n_devices: int, argv: Optional[List[str]] = None
) -> int:
    """Re-exec ``argv`` (default: this process's command line) in a child
    with an ``n_devices``-device virtual CPU platform forced at startup.
    Returns the child's exit code."""
    if is_reexec_child():
        import jax

        raise RuntimeError(
            f"re-exec'd child still sees {len(jax.devices())} devices "
            f"(< {n_devices}); virtual CPU platform did not take effect"
        )
    env = dict(os.environ)
    env[SENTINEL] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    want = f"--{_COUNT_FLAG}={n_devices}"
    flags = env.get("XLA_FLAGS", "")
    if _COUNT_FLAG in flags:
        flags = re.sub(rf"--{_COUNT_FLAG}=\d+", want, flags)
    else:
        flags = (flags + " " + want).strip()
    env["XLA_FLAGS"] = flags
    if argv is None:
        argv = [sys.executable, os.path.abspath(sys.argv[0]), *sys.argv[1:]]
    return subprocess.run(argv, env=env).returncode
