"""What the span readers under ``benchmark/layers/`` share: the trace
ring of the process under test, the measured window on the ring's clock,
and the three reductions (part of the yardstick: every PR computes them
the same way).

A ring record is a dict with ``name``, ``t0`` (its start, seconds on
``time.perf_counter()``), ``dur`` (microseconds), ``tid`` and ``args``.
The engine records one ``llm/pass`` per loop iteration that did work,
and inside it, one after another, the phases ``llm/admit``,
``llm/grant``, ``llm/dispatch``, ``llm/fence_wait`` and ``llm/drain``
(``bigdl_tpu/llm/serving.py``). A reader gets ``run`` and the process,
not the server and not the window's bounds, so it finds the window
itself; it returns ``None``, and the metric is left out, where there is
nothing to read: a program that records no such span, a ring that
dropped records, observability switched off.
"""

from __future__ import annotations

import bisect
import sys
from typing import Dict, List, Optional, Tuple

from benchmark import stats

Record = Dict[str, object]


def window(run: dict) -> Optional[Tuple[float, float]]:
    """[open, close) of the measured window on ``time.perf_counter()``:
    it opened ``setup_s`` after ``benchmark/run.py`` read ``T_START``
    and lasted ``counters["t"]``."""
    for mod in ("__main__", "benchmark.run"):
        t_start = getattr(sys.modules.get(mod), "T_START", None)
        if t_start is not None:
            break
    else:
        return None
    try:
        t_open = t_start + run["e2e"]["setup_s"]
        return t_open, t_open + run["counters"]["t"]
    except KeyError:
        return None


def ring() -> Optional[List[Record]]:
    """The process's span records in arrival order, or ``None`` when
    they cannot be trusted to be all of them."""
    from bigdl_tpu import observability as obs
    if not obs.enabled() or obs.TRACE.dropped:
        return None
    return obs.TRACE.spans()


def _named(records: List[Record], name: str) -> List[Record]:
    return [r for r in records
            if r["name"] == name and r.get("t0") is not None]


def _inside(r: Record, t_open: float, t_close: float) -> bool:
    return t_open <= r["t0"] < t_close


def pass_host_ms(records: List[Record], t_open: float, t_close: float
                 ) -> Optional[float]:
    """Mean over the ``llm/pass`` records that start in the window of
    the pass's duration less the ``llm/fence_wait`` phases that start
    inside it: all the engine thread does for a pass but wait for the
    device."""
    passes = [p for p in _named(records, "llm/pass")
              if _inside(p, t_open, t_close)]
    if not passes:
        return None
    waits = sorted((w["t0"], w["dur"], w["tid"])
                   for w in _named(records, "llm/fence_wait"))
    starts = [w[0] for w in waits]
    total = 0.0
    for p in passes:
        lo = bisect.bisect_left(starts, p["t0"])
        hi = bisect.bisect_left(starts, p["t0"] + p["dur"] / 1e6)
        total += p["dur"] - sum(dur for _, dur, tid in waits[lo:hi]
                                if tid == p["tid"])
    return total / len(passes) / 1e3


def admit_ms(records: List[Record], t_open: float, t_close: float
             ) -> Optional[float]:
    """Mean duration of the ``llm/admit`` phases that gave at least one
    request a slot, over the passes that start in the window (the
    admission sweep is a pass's first phase, so it starts with it)."""
    durs = [a["dur"] for a in _named(records, "llm/admit")
            if _inside(a, t_open, t_close)
            and a["args"].get("admitted", 0) >= 1]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3


def itl_ms(records: List[Record], t_open: float, t_close: float
           ) -> List[float]:
    """Gaps between consecutive ``llm/drain`` ends that name the same
    request, for the gaps that close in the window: the token gaps as
    the engine thread itself saw them."""
    ends: Dict[object, List[float]] = {}
    for d in _named(records, "llm/drain"):
        for rid in d["args"].get("requests", ()):
            ends.setdefault(rid, []).append(d["t0"] + d["dur"] / 1e6)
    gaps: List[float] = []
    for stamps in ends.values():
        gaps.extend(stats.gaps_in_window(sorted(stamps), t_open, t_close))
    return [g * 1e3 for g in gaps]


def read(run: dict, reduce_) -> Optional[float]:
    """``reduce_(records, t_open, t_close)`` over this process's ring
    and this run's window, or ``None``."""
    win, records = window(run), ring()
    if win is None or records is None:
        return None
    return reduce_(records, *win)
