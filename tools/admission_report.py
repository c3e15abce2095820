"""One run of a benchmark cell in THIS process, then a report from the
trace ring it leaves (ISSUE 35): what ``benchmark/run.py`` prints comes
first and unchanged, the report goes to a file.

    python3 tools/admission_report.py <report.json> [--watch] -- \\
        --workload <cell> --seed <n> --seconds 51 --trace <0|1>

The report: how full the ring is; a prefill's three children
(``llm/prefill_stage`` / ``_dispatch`` / ``_finish``) by bucket; the
engine-side token gaps by percentile, which of them lie behind a
prefill, and for the band p93-p97 what the others hold more of than a
median gap (by pass phase, ``eager_us``, ``py/gc``); the collector's
counts. ``--watch`` adds a thread that sleeps a millisecond at a time
and keeps every period over 20 ms with the engine thread's records that
cover it: an all-thread stall as the benchmark's poller sees one.
"""

from __future__ import annotations

import bisect
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402  (reads T_START)
from benchmark import stats  # noqa: E402

PHASES = ("llm/admit", "llm/grant", "llm/dispatch", "llm/fence_wait",
          "llm/drain")
CHILDREN = ("llm/prefill_stage", "llm/prefill_dispatch",
            "llm/prefill_finish")


def throttled():
    """(times throttled, microseconds throttled) of this process's
    control group so far, or ``None`` where the machine does not say: a
    group that has spent its CPU quota is stopped, every thread of it,
    until the scheduler's next period (100 ms by default)."""
    for path in ("/sys/fs/cgroup/cpu.stat",
                 "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            with open(path) as f:
                stat = dict(line.split() for line in f)
        except OSError:
            continue
        usec = stat.get("throttled_usec")
        if usec is None and "throttled_time" in stat:
            usec = int(stat["throttled_time"]) // 1000
        if usec is not None:
            return int(stat.get("nr_throttled", 0)), int(usec)
    return None


class Watch(threading.Thread):
    def __init__(self, over: float = 0.02):
        super().__init__(name="report-watch", daemon=True)
        self.over, self.halt, self.long = over, threading.Event(), []

    def run(self):
        last, was = time.perf_counter(), throttled()
        while not self.halt.is_set():
            time.sleep(0.001)
            now = time.perf_counter()
            if now - last > self.over:
                is_ = throttled()
                self.long.append((last, now, None if was is None else
                                  (is_[1] - was[1]) / 1e3))
                was = is_
            last = now


def _end(r):
    return r["t0"] + r["dur"] / 1e6


def _stats(ms):
    return {"n": len(ms), "mean_ms": sum(ms) / len(ms), "max_ms": max(ms),
            "p50_ms": stats.percentile(ms, 50)} if ms else {"n": 0}


def _overlaps(index, lo, hi):
    """Seconds of ``[lo, hi)`` under each name of ``index`` = (starts,
    records sorted by start). A record that began up to 64 records
    before ``lo`` is still found (a sweep's children, a long wait)."""
    starts, recs = index
    out = {}
    k = max(0, bisect.bisect_left(starts, lo) - 64)
    while k < len(recs) and recs[k]["t0"] < hi:
        r = recs[k]
        o = min(hi, _end(r)) - max(lo, r["t0"])
        if o > 0:
            out[r["name"]] = out.get(r["name"], 0.0) + o
        k += 1
    return out


def report(t_open, t_close, watch):
    from bigdl_tpu import observability as obs
    from bigdl_tpu.observability import tracing
    ring = obs.TRACE
    recs = [r for r in ring.spans() if r.get("t0") is not None]
    out = {"ring": {"held": len(ring), "capacity": ring.capacity,
                    "dropped": ring.dropped,
                    "begun_before_close": sum(r["t0"] < t_close
                                              for r in recs)},
           "gc": {"collections_total": list(getattr(
                      tracing, "gc_collections_total", ())),
                  "seconds_total": list(getattr(
                      tracing, "gc_seconds_total", ())),
                  "records_in_window": [
                      {"at_s": r["t0"] - t_open, "ms": r["dur"] / 1e3,
                       **r["args"]} for r in recs if r["name"] == "py/gc"
                      and t_open <= r["t0"] < t_close]}}
    passes = [r for r in recs if r["name"] == "llm/pass"]
    if not passes:
        return out
    tid = passes[-1]["tid"]
    mine = sorted((r for r in recs if r["tid"] == tid),
                  key=lambda r: r["t0"])
    inside = [r for r in mine if t_open <= r["t0"] < t_close]
    by = {}
    for r in inside:
        by.setdefault(r["name"], []).append(r)
    out["records_in_window"] = {k: len(v) for k, v in sorted(by.items())}

    # (i) the children by bucket, and the sweep around them
    bucket_of = {r["args"]["request"]: r["args"]["bucket"]
                 for r in by.get("llm/prefill_stage", ())}
    kids = {}
    for name in CHILDREN:
        for r in by.get(name, ()):
            b = bucket_of.get(r["args"].get("request"), "chunk")
            kids.setdefault(str(b), {}).setdefault(name, []).append(
                r["dur"] / 1e3)
    out["children_by_bucket"] = {
        b: {n: _stats(v) for n, v in d.items()}
        for b, d in sorted(kids.items(), key=lambda kv: str(kv[0]))}
    out["children"] = {n: _stats([r["dur"] / 1e3 for r in by.get(n, ())])
                       for n in CHILDREN + ("llm/prefill", "llm/admit")}
    # the sweeps that seated several, or whose epilogue waited: what a
    # prefill's finish costs by its place in the sweep
    sweeps = []
    for a in by.get("llm/admit", ()):
        kids_ = [r for r in inside if r["name"] in CHILDREN
                 and a["t0"] <= r["t0"] < _end(a)]
        fins = [r for r in kids_ if r["name"] == "llm/prefill_finish"]
        if len(fins) > 1 or any(f["dur"] > 20e3 for f in fins):
            sweeps.append({
                "at_s": a["t0"] - t_open, "ms": a["dur"] / 1e3,
                "prefills": [[bucket_of.get(f["args"]["request"]),
                              round(f["dur"] / 1e3, 2)] for f in fins]})
    out["sweeps_with_several_or_a_long_finish"] = sweeps
    calls_ = [r for r in mine if r["name"] == "llm/prefill_dispatch"]
    top = sorted(by.get("llm/grant", ()),
                 key=lambda r: -r["args"].get("eager_us", 0.0))[:6]
    out["grant_eager_top"] = []
    for g in top:
        before = [c for c in calls_ if c["t0"] < g["t0"]]
        out["grant_eager_top"].append({
            "eager_ms": g["args"].get("eager_us", 0.0) / 1e3,
            "at_s": g["t0"] - t_open,
            "last_prefill": None if not before else {
                "bucket": before[-1]["args"]["bucket"],
                "ms_ago": (g["t0"] - _end(before[-1])) * 1e3}})
    for name in ("llm/grant", "llm/drain"):
        us = [r["args"].get("eager_us") for r in by.get(name, ())]
        us = [u for u in us if u is not None]
        if us:
            out.setdefault("eager_us", {})[name] = {
                "n": len(us), "nonzero": sum(u > 0 for u in us),
                "mean": sum(us) / len(us), "p99": stats.percentile(us, 99),
                "max": max(us)}

    # (ii) the engine-side gaps
    phases = [r for r in mine if r["name"] in PHASES]
    index = ([r["t0"] for r in phases], phases)
    calls = [r["t0"] for r in calls_]
    gcs = sorted((r for r in recs if r["name"] == "py/gc"),
                 key=lambda r: r["t0"])
    gindex = ([r["t0"] for r in gcs], gcs)
    ends = {}
    for d in (r for r in mine if r["name"] == "llm/drain"):
        for rid in d["args"].get("requests", ()):
            ends.setdefault(rid, []).append((_end(d), d))
    gaps = []
    for stamps in ends.values():
        stamps.sort(key=lambda e: e[0])
        for (a, _), (b, d) in zip(stamps, stamps[1:]):
            if t_open <= b < t_close:
                behind = bisect.bisect_left(calls, b) \
                    > bisect.bisect_left(calls, a)
                gaps.append((b - a, a, b, behind, d))
    if gaps:
        gaps.sort(key=lambda g: g[0])
        n = len(gaps)
        out["gaps"] = {
            "n": n, "behind_prefill": sum(g[3] for g in gaps),
            "by_counter": {
                "gaps": sum(d["args"].get("gaps", 0)
                            for d in by.get("llm/drain", ())),
                "gaps_behind_prefill": sum(
                    d["args"].get("gaps_behind_prefill", 0)
                    for d in by.get("llm/drain", ()))},
            "percentiles_ms": {str(q): stats.percentile([g[0] for g in gaps], q) * 1e3
                               for q in (50, 75, 90, 93, 94, 95, 96, 97,
                                         98, 99)}}
        first = next((k for k, g in enumerate(gaps) if g[3]), None)
        if first is not None:
            out["gaps"]["first_behind_prefill"] = {
                "percentile": 100.0 * (first + 1) / n,
                "ms": gaps[first][0] * 1e3}
        # behind-prefill share by percentile point from p90 up
        out["gaps"]["behind_by_point"] = {
            str(q): [sum(g[3] for g in gaps[n * q // 100:
                                            n * (q + 1) // 100]),
                     n * (q + 1) // 100 - n * q // 100]
            for q in range(90, 100)}

        def parts(sel):
            tot, eager = {}, 0.0
            for dur, a, b, _, d in sel:
                o = _overlaps(index, a, b)
                o["py/gc"] = sum(_overlaps(gindex, a, b).values())
                o["(between phases)"] = dur - sum(
                    v for k, v in o.items() if k != "py/gc")
                for k, v in o.items():
                    tot[k] = tot.get(k, 0.0) + v
                eager += d["args"].get("eager_us", 0.0)
            m = max(1, len(sel))
            res = {k: v / m * 1e3 for k, v in sorted(tot.items())}
            res["drain eager_us"] = eager / m
            res["n"] = len(sel)
            res["gap_ms"] = sum(g[0] for g in sel) / m * 1e3
            return res

        band = gaps[n * 93 // 100: n * 97 // 100]
        out["gaps"]["band_p93_p97"] = {
            "n": len(band), "behind_prefill": sum(g[3] for g in band),
            "not_behind_mean_ms": parts([g for g in band if not g[3]]),
            "median_band_p45_p55_mean_ms": parts(
                gaps[n * 45 // 100: n * 55 // 100])}

    # (iii) long periods of a sleeping thread and what covers them
    if watch is not None:
        eindex = ([r["t0"] for r in mine], mine)
        longs = []
        for a, b, throttled_ms in watch.long:
            if not t_open <= a < t_close:
                continue
            cover = {k: v * 1e3 for k, v in _overlaps(eindex, a, b).items()
                     if k != "llm/pass"}
            longs.append({"at_s": a - t_open, "ms": (b - a) * 1e3,
                          # of the control group, since the long period
                          # before this one
                          "throttled_ms_since_last": throttled_ms,
                          "other_threads": sorted(
                              {r["name"] for r in recs if r["tid"] != tid
                               and r["t0"] < b and _end(r) > a}),
                          "engine_thread_ms": {
                              k2: round(v, 2) for k2, v in sorted(
                                  cover.items(), key=lambda kv: -kv[1])},
                          "py_gc_ms": sum(_overlaps(gindex, a, b).values())
                          * 1e3})
        longs.sort(key=lambda e: -e["ms"])
        out["long_periods_over_20ms"] = {"n": len(longs),
                                         "longest": longs[:12]}

    return out


if __name__ == "__main__":
    path, rest = sys.argv[1], sys.argv[2:]
    watch = None
    if rest and rest[0] == "--watch":
        watch, rest = Watch(), rest[1:]
        watch.start()
    if rest and rest[0] == "--":
        rest = rest[1:]

    # keep what the cell's driver hands back: the window is found from
    # it, as the span readers find it
    from benchmark import manifest as mf
    from benchmark import spans
    kept = {}
    driver_of = mf.driver_of

    def keeping(config):
        driver = driver_of(config)

        class Kept:
            @staticmethod
            def run(ctx):
                kept["run"] = driver.run(ctx)
                return kept["run"]
        return Kept

    mf.driver_of = keeping
    before = throttled()
    rc = bench_run.main(rest)
    after = throttled()
    if watch is not None:
        watch.halt.set()
    win = spans.window(kept["run"]) if "run" in kept else None
    if win is not None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        out = report(*win, watch)
        out["cgroup_throttled_in_process"] = None if before is None else {
            "times": after[0] - before[0],
            "ms": (after[1] - before[1]) / 1e3}
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    sys.exit(rc)
