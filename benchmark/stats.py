"""The arithmetic that turns stamps into metrics (part of the yardstick)."""

from __future__ import annotations

import math
import re
from typing import Dict, Iterable, List, Sequence

_NAMED = re.compile(r"^([a-z]+)_(p(\d{1,2})|mean)_ms$")


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]): the smallest sample with
    at least q% of the samples at or below it. No interpolation, so the
    value is always one that was observed."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def gaps_in_window(stamps: Iterable[float], t0: float, t1: float
                   ) -> List[float]:
    """Gaps between consecutive stamps of ONE request; a gap counts when
    the stamp that closes it lies in [t0, t1)."""
    out, prev = [], None
    for t in stamps:
        if prev is not None and t0 <= t < t1:
            out.append(t - prev)
        prev = t
    return out


def named(name: str, samples_ms: Dict[str, Sequence[float]]) -> float:
    """The latency metric ``<family>_p<q>_ms`` or ``<family>_mean_ms``
    over that family's samples in milliseconds (``ttft_p90_ms``,
    ``itl_p95_ms``, ``ttft_mean_ms``): a manifest entry of that form
    needs no code. ``KeyError`` for any other name or family."""
    m = _NAMED.match(name)
    if not m or m.group(1) not in samples_ms:
        raise KeyError(f"no latency metric {name!r}: want <family>_p<q>_ms "
                       f"or <family>_mean_ms, family in {sorted(samples_ms)}")
    values = samples_ms[m.group(1)]
    if m.group(2) == "mean":
        return math.fsum(values) / len(values)
    return percentile(values, float(m.group(3)))
