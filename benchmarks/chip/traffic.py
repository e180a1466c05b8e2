"""The one traffic generator: a mix file's parameters in, a request list out.

Every run gets the same work. Lengths are the quantiles of the mix's length
distribution at ``(i + 0.5) / n`` and gaps the quantiles of its arrival
distribution, put in an order that is the mix's own (``schedule_seed``), so
the window replays one fixed schedule of arrivals and sizes, as a recorded
trace would be replayed. ``--seed`` draws the token ids of every prompt. A
tail over one window's requests swings with the order of the work far more
than with anything a change to the program does, so the order is held fixed.

Mix keys (``traffic/<name>.json``):

* ``arrivals``: ``"poisson"`` (exponential gaps at ``rate_per_s``),
  ``"gamma"`` (gamma gaps of shape ``gamma_shape`` and mean ``1/rate_per_s``:
  bursts when the shape is below 1), or ``"backlog"`` (``requests`` due at
  once when the window opens);
* ``prompt_len`` / ``output_len``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal, clipped;
* ``shared_prefix`` (optional): ``{"tokens", "groups"}``: each prompt starts
  with one of ``groups`` seeded prefixes of ``tokens`` tokens;
* ``schedule_seed`` (optional, 0): which fixed order the schedule takes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Request:
    idx: int
    due: float  # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of a clipped lognormal (ascending)."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = [spec["median"] * math.exp(spec["sigma"] * zi) for zi in z]
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _gamma_quantiles(shape: float, n: int, rng_draws: int = 200_000) -> np.ndarray:
    # quantiles of a unit-mean gamma from a fixed sample (no scipy here)
    sample = np.sort(np.random.default_rng(0).gamma(shape, 1.0 / shape, rng_draws))
    return sample[((np.arange(n) + 0.5) / n * rng_draws).astype(np.int64)]


def gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps, unit mean, at their distribution's quantiles."""
    kind = mix["arrivals"]
    q = (np.arange(n) + 0.5) / n
    if kind == "poisson":
        g = -np.log1p(-q)
    elif kind == "gamma":
        g = _gamma_quantiles(float(mix["gamma_shape"]), n)
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    return g / g.mean()


def make_requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """The requests due in a window of ``seconds``, sorted by due time."""
    order = np.random.default_rng((int(mix.get("schedule_seed", 0)), 0x6F72))
    rng = np.random.default_rng((seed, 0x7261))
    if mix["arrivals"] == "backlog":
        n = int(mix["requests"])
        due = np.zeros(n)
    else:
        n = max(1, round(float(mix["rate_per_s"]) * seconds))
        g = order.permutation(gaps(mix, n)) * (seconds / n)
        due = np.cumsum(g) - g[0] / 2  # the first due a half gap in, the last before the close
    plen = order.permutation(lengths(mix["prompt_len"], n))
    olen = order.permutation(lengths(mix["output_len"], n))
    prefixes = None
    sp = mix.get("shared_prefix")
    if sp:
        prefixes = rng.integers(0, vocab, (int(sp["groups"]), int(sp["tokens"])), dtype=np.int64)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(plen[i]), dtype=np.int64)
        if prefixes is not None:
            k = min(prefixes.shape[1], int(plen[i]) - 1)
            toks[:k] = prefixes[rng.integers(0, prefixes.shape[0])][:k]
        out.append(Request(i, float(due[i]), toks.astype(np.int32), int(olen[i])))
    return out
