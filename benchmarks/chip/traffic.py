"""The one traffic generator.  A mix is a data file, ``traffic/<mix>.json``;
this module turns it and a seed into requests.

Every seed gives the same work in another order: each phase of a run holds
a fixed number of requests, the lengths come in exact quotas of their
classes, and the gaps between arrivals are a fixed set (the quantiles of
the arrival law) shuffled by the seed.  Only the order, the pairing of
lengths and the token ids depend on the seed, so runs with different seeds
spread no more than two runs of one seed.

Mix keys (all optional unless a driver needs them):

* ``arrival``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop) or
  ``{"kind": "closed", "clients": c}`` (each client sends after its reply).
* ``prompt_tokens`` / ``output_tokens``: ``{"values": [...], "weights":
  [...]}`` classes with their shares.
* ``ramp_s``, ``tail_s``: traffic of the same mix before the window (it
  brings the system to steady state) and after it (it keeps the load on
  while the window's requests finish).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence

import numpy as np

from .common import HERE, load_json

PHASES = ("ramp", "window", "tail")


def load_mix(name: str) -> Dict[str, Any]:
    return load_json(HERE / "traffic" / f"{name}.json")


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for one purpose of one seed (seeds may exceed 32
    bits: SeedSequence takes any non-negative integer)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def quotas(n: int, weights: Sequence[float]) -> List[int]:
    """Split n into integer counts proportional to ``weights`` (largest
    remainder), so every seed draws the same multiset of classes."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    counts = np.floor(raw).astype(int)
    order = np.argsort(-(raw - counts), kind="stable")
    for i in order[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def class_draw(spec: Dict[str, Any], n: int,
               rng: np.random.Generator) -> np.ndarray:
    values = np.repeat(np.asarray(spec["values"], np.int64),
                       quotas(n, spec["weights"]))
    return rng.permutation(values)


def arrival_gaps(arrival: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """n gaps (seconds) before each arrival of an open loop."""
    if arrival["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / float(arrival["rate_per_s"]))


@dataclasses.dataclass
class LMRequest:
    phase: str
    due_s: float            # scheduled arrival, seconds after the ramp starts
    prompt: np.ndarray      # int32 token ids
    output_tokens: int


def lm_requests(mix: Dict[str, Any], seed: int, vocab: int,
                window_s: float) -> List[LMRequest]:
    """Every request of a run, in arrival order: the ramp, the window and
    the tail, each a fixed amount of work from the mix."""
    arrival = mix["arrival"]
    rate = float(arrival["rate_per_s"])
    tok_rng = rng_for(seed, 1)
    out: List[LMRequest] = []
    start = 0.0
    for p, phase in enumerate(PHASES):
        dur = float(window_s if phase == "window" else mix.get(f"{phase}_s",
                                                                0.0))
        n = int(round(rate * dur))
        if n == 0:
            start += dur
            continue
        rng = rng_for(seed, 2, p)
        gaps = arrival_gaps(arrival, n, rng)
        plens = class_draw(mix["prompt_tokens"], n, rng)
        olens = class_draw(mix["output_tokens"], n, rng)
        due = start + np.cumsum(gaps) - gaps
        for i in range(n):
            body = tok_rng.integers(0, vocab, int(plens[i]), dtype=np.int32)
            out.append(LMRequest(phase, float(due[i]), body, int(olens[i])))
        start += dur
    return out


def max_total_tokens(mix: Dict[str, Any]) -> int:
    return (max(mix["prompt_tokens"]["values"])
            + max(mix["output_tokens"]["values"]))


def window_bounds(mix: Dict[str, Any], window_s: float):
    ramp = float(mix.get("ramp_s", 0.0))
    return ramp, ramp + window_s


def ceil_to(x: int, m: int) -> int:
    return int(math.ceil(x / m) * m)
