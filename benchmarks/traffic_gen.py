"""The one general traffic generator: a mix is a data file of parameters.

A serve mix gives the arrival process and rate, the shared prefix, and the
distributions of the prompt tails and output budgets. The lengths and the
inter-arrival gaps are evenly spaced quantiles of the stated distributions,
put in an order drawn from the mix's own `schedule_seed`: every run of a cell
replays the same arrivals with the same lengths, as a recorded trace would,
because the order alone moved a tail by a factor of two (PERF.md). `--seed`
draws the token ids (and the weights): other inputs, the same work.

Arrival processes and length distributions are the named functions of
`ARRIVALS` and `LENGTHS`; a mix picks them by name, so a mix of these is a
data file and nothing else.

The arithmetic of the Poisson gaps follows the program's `serve/traffic.py`
(exponential gaps at `rate_rps`); it is copied here so that a later change to
the program cannot change the yardstick.
"""
import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class TimedPrompt:
    uid: str
    due_s: float           # seconds from the window's start
    prompt: tuple          # token ids
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(spec, n):
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    return spec["median"] * np.exp(spec["sigma"] * z)


#: length distributions by name: (spec, n) -> n evenly spaced quantiles
LENGTHS = {
    "lognormal": _lognormal,
    "uniform": lambda spec, n: spec["min"] + (spec["max"] - spec["min"]) * _quantiles(n),
    "fixed": lambda spec, n: np.full(n, spec["value"], float),
}


def _lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths: evenly spaced quantiles of the stated distribution,
    clipped to [min, max]."""
    if spec["dist"] not in LENGTHS:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = LENGTHS[spec["dist"]](spec, n)
    lo = spec.get("min", 1)
    hi = spec.get("max", max(int(raw.max()), lo))
    return np.clip(np.rint(raw), lo, hi).astype(int)


def _poisson_gaps(n: int, span: float, rng) -> np.ndarray:
    """`n` arrivals inside `span` seconds, the first at 0: the gaps are the
    exponential distribution's evenly spaced quantiles in an order drawn from
    `rng`, so every order has the same n-1 gaps."""
    if n <= 1:
        return np.zeros(n)
    gaps = -np.log1p(-_quantiles(n - 1))
    gaps *= span * (n - 1) / n / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))])


def _bursty(mix, n, seconds, rng):
    """Silent except for `burst_secs` at the top of every `burst_period_s`
    (the program's `serve/traffic.py` shape): the n arrivals are dealt evenly
    over the window's bursts, Poisson gaps inside each."""
    period, secs = float(mix["burst_period_s"]), float(mix["burst_secs"])
    starts = np.arange(0.0, seconds, period)
    sizes = [len(part) for part in np.array_split(np.arange(n), len(starts))]
    return np.concatenate([
        start + _poisson_gaps(size, min(secs, seconds - start), rng)
        for start, size in zip(starts, sizes)])


#: arrival processes by name: (mix, n, seconds, rng) -> n due times, sorted
ARRIVALS = {
    "backlog": lambda mix, n, seconds, rng: np.zeros(n),
    "uniform": lambda mix, n, seconds, rng: np.arange(n) * (seconds / n),
    "poisson": lambda mix, n, seconds, rng: _poisson_gaps(n, seconds, rng),
    "bursty": _bursty,
}


def _arrivals(mix: dict, n: int, seconds: float, rng) -> np.ndarray:
    if mix["arrival"] not in ARRIVALS:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    return ARRIVALS[mix["arrival"]](mix, n, seconds, rng)


def serve_schedule(mix: dict, *, vocab_size: int, seed: int,
                   seconds: float) -> List[TimedPrompt]:
    """All requests due in a window of `seconds`, in due order."""
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    order = np.random.default_rng([int(mix["schedule_seed"]), 1])
    tails = order.permutation(_lengths(mix["tail"], n))
    budgets = order.permutation(_lengths(mix["output"], n))
    due = _arrivals(mix, n, seconds, order)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    prefix = rng.integers(1, vocab_size, mix.get("shared_prefix_tokens", 0))
    out = []
    for i in range(n):
        tail = rng.integers(1, vocab_size, int(tails[i]))
        out.append(TimedPrompt(
            uid=f"r{i:05d}",
            due_s=float(due[i]),
            prompt=tuple(int(t) for t in np.concatenate([prefix, tail])),
            max_new_tokens=int(budgets[i]),
        ))
    return out


def train_batch(job: dict, *, vocab_size: int, seed: int, step: int,
                rows: int) -> np.ndarray:
    """The token batch of one training step: fresh rows that all differ,
    drawn from the seed and the step number."""
    rng = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 2, int(step)])
    return rng.integers(0, vocab_size, (rows, job["seq_len"])).astype(np.int32)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over ALL the values given (q in 0..100)."""
    if not len(values):
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(int(math.ceil(q / 100.0 * len(ordered))), 1)
    return float(ordered[rank - 1])
