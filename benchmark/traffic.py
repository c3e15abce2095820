"""The one general traffic generator. A mix is a data file,
``benchmark/traffic/<name>.json``; this module turns it and ``--seed``
into a schedule. A new mix needs a new file and no new code.

Every seed offers the SAME set of sizes and gaps in another order: the
prompt and output lengths are the quantile grid of the file's
distributions and the gaps the quantile grid of an exponential law
(Poisson-like arrivals with a fixed count), and the seed shuffles each
of the three (and draws every token id). Tails of a queue depend on how
much work a window holds; this way two seeds differ in what meets what
and in nothing else.

Keys of a mix (``"loop": "open"``, the only kind so far): arrivals on a
schedule at ``rate_per_s``; the ``lead_in_s`` seconds before the window
opens are a phase of their own (part of set-up: the window opens on a
steady batch), so the window ``[lead_in_s, lead_in_s + seconds)`` always
holds exactly ``round(rate_per_s * seconds)`` requests. ``prompt``,
``output``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` |
``{"dist": "uniform", "min", "max"}`` | ``{"dist": "const", "value"}``,
in tokens.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List

import numpy as np


def quantile_grid(spec: Dict, n: int, scale: float = 1.0) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 0.5) / n of ``spec``, sorted,
    whole numbers. ``scale`` shrinks lengths for the CPU rehearsal."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        v = np.clip(v, spec["min"], spec["max"])
    elif kind == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "const":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown dist {kind!r}")
    return np.maximum(2, np.rint(v * scale)).astype(np.int64)


def exponential_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` gaps at the quantiles of an exponential law, scaled to sum
    to ``total_s`` exactly."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (total_s / g.sum())


def requests(mix: Dict, seed: int, seconds: float, vocab: int,
             scale: float = 1.0) -> List[Dict]:
    """The run's requests in sending order, each ``{"prompt": int32 ids,
    "max_new": int, "due": seconds from the schedule's start}``: the
    lead-in phase, then the window's."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng(seed)
    out, start = [], 0.0
    for length in (float(mix["lead_in_s"]), float(seconds)):
        if length <= 0:
            continue
        n = max(1, round(mix["rate_per_s"] * length))
        gaps = exponential_gaps(n, length)          # gap i follows request i
        prompts = quantile_grid(mix["prompt"], n, scale)
        outputs = quantile_grid(mix["output"], n, scale)
        for a in (gaps, prompts, outputs):
            rng.shuffle(a)
        due = start + np.cumsum(gaps) - gaps
        out += [{"prompt": rng.integers(0, vocab, p, dtype=np.int32),
                 "max_new": int(o), "due": float(t)}
                for p, o, t in zip(prompts, outputs, due)]
        start += length
    return out


def prefill_buckets(mix: Dict, page: int, scale: float = 1.0) -> List[int]:
    """The power-of-two prefill buckets (>= one page) the mix's prompt
    lengths fall in: what a serving driver warms, and nothing else."""
    lens = quantile_grid(mix["prompt"], 256, scale)
    return sorted({max(page, 1 << (int(t) - 1).bit_length())
                   for t in lens})
