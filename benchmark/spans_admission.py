"""Reductions over the spans that name an admission and a late token gap
from inside the engine (ISSUE 35), beside ``benchmark/spans.py`` and
with its conventions: a record is a dict with ``name``, ``t0`` (seconds
on ``time.perf_counter()``), ``dur`` (microseconds), ``tid``, ``args``;
a reduction takes the records and the window and returns ``None`` where
the program recorded nothing to read, as the parent commit has not.

A whole-prompt prefill inside ``llm/admit`` is three records one after
another on the engine thread: ``llm/prefill_stage`` (entry to the jit
call: host work the device idles through, the step in flight having
been drained ahead of the sweep), ``llm/prefill_dispatch`` (the jit
call), ``llm/prefill_finish`` (the eager table updates, which queue
behind the prefill just dispatched). ``llm/drain`` carries ``gaps`` and
``gaps_behind_prefill``, the token gaps it closed and those among them
with a prefill dispatched since the row's previous token. ``py/gc`` is
one garbage collection of 0.5 ms or more, on whichever thread ran it.
"""

from __future__ import annotations

from typing import List, Optional

from benchmark import spans
from benchmark.spans import Record, _inside, _named


def _end(r: Record) -> float:
    return r["t0"] + r["dur"] / 1e6


def stage_ms(records: List[Record], t_open: float, t_close: float
             ) -> Optional[float]:
    """Mean, over the ``llm/admit`` records that start in the window
    and prefilled somebody, of the time from the sweep's start to the
    end of the first ``llm/prefill_dispatch`` of that thread inside it:
    the host time an admission costs before the device has work
    again."""
    admits = [a for a in _named(records, "llm/admit")
              if _inside(a, t_open, t_close)
              and a["args"].get("prefills", 0) >= 1]
    calls = sorted(_named(records, "llm/prefill_dispatch"),
                   key=lambda r: r["t0"])
    waits = []
    for a in admits:
        first = next((c for c in calls if c["tid"] == a["tid"]
                      and a["t0"] <= c["t0"] < _end(a)), None)
        if first is not None:
            waits.append(_end(first) - a["t0"])
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3


def prefill_finish_ms(records: List[Record], t_open: float,
                      t_close: float) -> Optional[float]:
    """Mean duration of the ``llm/prefill_finish`` records that start in
    the window."""
    durs = [r["dur"] for r in _named(records, "llm/prefill_finish")
            if _inside(r, t_open, t_close)]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3


def gaps_behind_prefill_pct(records: List[Record], t_open: float,
                            t_close: float) -> Optional[float]:
    """100 × Σ ``gaps_behind_prefill`` ÷ Σ ``gaps`` over the
    ``llm/drain`` records that END in the window (a gap closes where
    its token is applied)."""
    drains = [d["args"] for d in _named(records, "llm/drain")
              if t_open <= _end(d) < t_close and "gaps" in d["args"]]
    gaps = sum(a["gaps"] for a in drains)
    if not gaps:
        return None
    return 100.0 * sum(a["gaps_behind_prefill"] for a in drains) / gaps


def gc_ms(records: List[Record], t_open: float, t_close: float) -> float:
    """Σ duration of the ``py/gc`` records of any thread that start in
    the window; 0.0 when there is none (whether the program watches its
    collector at all is the reader's to ask)."""
    return sum(r["dur"] for r in _named(records, "py/gc")
               if _inside(r, t_open, t_close)) / 1e3


def say_fill(run: dict) -> None:
    """One ``#`` line on how full the ring is: every span reader answers
    ``None`` once it has dropped a record."""
    from bigdl_tpu import observability as obs
    ring, win = obs.TRACE, spans.window(run)
    held = len(ring)
    line = (f"trace ring: {held} of {ring.capacity} records "
            f"({100.0 * held / max(1, ring.capacity):.1f} %)")
    if win is not None:
        early = sum(1 for r in ring.spans()
                    if r.get("t0") is not None and r["t0"] < win[1])
        line += f", {early} of them begun before the window closed"
    print(f"# {line}; dropped {ring.dropped}", flush=True)
