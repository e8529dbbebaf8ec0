"""The one traffic generator: reads a mix's parameters from its data file.

Lengths follow the published traces as ``repro.workloads.traces`` shapes
them (lognormal, sigma 0.6, mean as the trace's; copied, not imported), and
open-loop arrivals are Poisson: i.i.d. exponential gaps at the cell's rate.
Every run of a mix replays one schedule, drawn from the mix's
``schedule_seed`` and not from the run's seed, so the work in a window does
not move with the seed; the run's seed draws the prompts' tokens and the
weights.  Each request draws its gap, prompt and output in turn from one
stream, so a cell at another rate sends the same requests, closer together
or further apart.

A mix file holds::

    arrivals       "poisson" (open loop, rate from the cell) or "closed"
                   (``clients`` callers, each sending when its last is done)
    schedule_seed  the seed of the one schedule every run replays
    prompt         {"mean", "sigma", "min", "max", "grid"}: the length is
                   rounded up to the next value of ``grid`` (no grid: kept
                   as drawn)
    output         {"mean", "sigma", "min", "max"}
"""

from __future__ import annotations

import bisect
import dataclasses
import math

import numpy as np

POOL = 4096  # closed loop: requests drawn per run; callers take them in order


@dataclasses.dataclass
class Request:
    rid: int
    prompt_len: int
    n_out: int
    due: float | None  # seconds from the window's start; None: closed loop


def _lognormal(rng: np.random.Generator, spec: dict) -> int:
    sigma = float(spec.get("sigma", 0.6))
    mu = math.log(float(spec["mean"])) - sigma**2 / 2
    return min(max(int(rng.lognormal(mu, sigma)), int(spec["min"])), int(spec["max"]))


def _round_up(length: int, grid: list[int] | None) -> int:
    if not grid:
        return length
    grid = sorted(grid)
    return grid[min(bisect.bisect_left(grid, length), len(grid) - 1)]


def generate(mix: dict, cell: dict, seconds: float) -> list[Request]:
    """The requests of one run: for an open loop those due in the window,
    in order of due time; for a closed loop a pool the callers take in order."""
    rng = np.random.default_rng([int(mix["schedule_seed"]), 0x7EAF])
    if mix["arrivals"] == "poisson":
        mean_gap = 1.0 / float(cell["rate_rps"])
    elif mix["arrivals"] != "closed":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    grid = mix["prompt"].get("grid")
    out: list[Request] = []
    t = 0.0
    while True:
        gap = rng.standard_exponential()
        prompt = _round_up(_lognormal(rng, mix["prompt"]), grid)
        n_out = _lognormal(rng, mix["output"])
        if mix["arrivals"] == "closed":
            if len(out) == POOL:
                return out
            out.append(Request(len(out), prompt, n_out, None))
            continue
        t += gap * mean_gap
        if t >= seconds:
            return out
        out.append(Request(len(out), prompt, n_out, t))


def prompt_tokens(req: Request, vocab: int, seed: int) -> np.ndarray:
    """The prompt's token ids, drawn from the seed and the request's id."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x70C5, req.rid])
    return rng.integers(0, vocab, req.prompt_len, dtype=np.int32)


def used_prompt_lengths(mix: dict, cell: dict, seconds: float) -> list[int]:
    """Every prompt length a run of this mix sends: the shapes that set-up warms."""
    return sorted({r.prompt_len for r in generate(mix, cell, seconds)})
