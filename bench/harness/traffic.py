"""The one traffic generator: reads a mix from bench/traffic/<mix>.json.

Every seed gets the same sizes and arrival times, drawn from the mix's
own ``shape_seed``; ``--seed`` draws the prompt tokens (and, for closed
mixes, the order of the sessions). So two seeds do the same work, and
the spread between seeds is the system's, not the generator's. An open
mix keeps its order too: with a few dozen requests in a window, the
tail of time to first token depends on which sizes arrive together,
and a reshuffled order moved it by a third from seed to seed.

Kinds:

* ``closed``: ``sessions`` requests, all due before the window opens
  (they are admitted and prefilled during set-up).
* ``open``: Poisson arrivals at ``rate_per_s``, starting ``lead_s``
  before the window opens, until it closes: round(rate * lead) arrivals
  in the lead and round(rate * seconds) in the window, each group a
  Poisson process conditioned on its count (exponential gaps scaled to
  the group's span).

Lengths: ``{"dist": "uniform", "min", "max"}`` or ``{"dist":
"lognormal", "median", "sigma", "min", "max"}`` (clipped), and for
outputs also ``{"dist": "to_capacity"}``: decode until the cache is
full. ``max_context`` caps prompt + output.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Arrival:
    uid: int
    due: float              # seconds after the window opens (<= 0: set-up)
    prompt: np.ndarray      # (S,) int32
    max_new: int
    temperature: float = 0.0


def _lengths(dist: dict, n: int, rng) -> np.ndarray:
    kind = dist["dist"]
    if kind == "uniform":
        x = rng.integers(dist["min"], dist["max"] + 1, size=n)
    elif kind == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"],
                              size=n))
        x = np.clip(np.rint(x), dist["min"], dist["max"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return x.astype(np.int64)


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xB3])


def _group(n: int, span: float, shape) -> np.ndarray:
    """n arrival times in [0, span): exponential gaps, scaled so that
    n + 1 of them fill the span."""
    gaps = shape.exponential(1.0, size=n + 1)
    return np.cumsum(gaps)[:n] * span / float(np.sum(gaps))


def generate(mix: dict, *, seed: int, seconds: float, vocab: int,
             capacity: int) -> list:
    """Arrivals sorted by due time."""
    shape = np.random.default_rng(mix["shape_seed"])
    order = seed_rng(seed)
    if mix["kind"] == "closed":
        groups = [np.full(int(mix["sessions"]), -math.inf)]
    elif mix["kind"] == "open":
        rate, lead = float(mix["rate_per_s"]), float(mix["lead_s"])
        n_lead, n_win = round(rate * lead), round(rate * seconds)
        groups = [_group(n_lead, lead, shape) - lead,
                  _group(n_win, seconds, shape)]
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    max_ctx = min(int(mix.get("max_context", capacity)), capacity)
    out = mix["output"]
    arrivals = []
    for due in groups:
        n = len(due)
        prompts = _lengths(mix["prompt"], n, shape)
        outputs = (None if out["dist"] == "to_capacity"
                   else _lengths(out, n, shape))
        idx = (order.permutation(n) if mix["kind"] == "closed"
               else range(n))
        for d, i in zip(due, idx):
            s = int(min(prompts[i], max_ctx - 1))
            m = capacity - s if outputs is None else int(outputs[i])
            m = max(1, min(m, max_ctx - s))
            toks = order.integers(0, vocab, size=s, dtype=np.int32)
            arrivals.append(Arrival(
                uid=len(arrivals), due=float(d), prompt=toks, max_new=m,
                temperature=float(mix.get("temperature", 0.0))))
    return arrivals
