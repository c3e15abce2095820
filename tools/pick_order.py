"""Which ``order_seed`` a serving cell's traffic file should carry: a
model of the engine's passes over each candidate order, no chip and no
JAX. A decode step costs what its live rows make it move, so the order
in which long and short answers are dealt sets the rows decoding and
with them every gap; the cell wants the order whose 95th percentile
lies deepest inside a stretch of equal steps (PERF.md section 6, PRs 27,
31, 33, 37).

    python3 tools/pick_order.py <traffic file> <first seed> <count> \\
        --step-ms A --row-ms B [--expert-ms C --experts E --per-row K] \\
        [--prefill-ms P --prefill-tok-s R] [--slots N] [--slice 33,39]

The step: ``A + B rows + C E (1 - exp(-rows K / E))`` ms (the last term
the held experts a batch of ``rows`` touches, ``K`` assignments a row
falling on ``E`` held experts); a prefill ``P + tokens / R`` ms, inside
the pass that admits it, felt by every live row. Prints, a seed: the
rows decoding in the mean, p50 / p95 / p99 of the gaps in the window,
how far p93 .. p97 spread around p95, how many percentile points p95
lies inside the stretch of steps within 0.3 % of it, the share of gaps
that hold a prefill, and the arrivals inside the traced slice; last,
the seeds ranked by that depth."""

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import traffic  # noqa: E402


def simulate(mix, seed, seconds, a):
    reqs = traffic.requests(mix, seed, seconds, 2)
    lead = float(mix["lead_in_s"])
    t, i, waiting, live = 0.0, 0, [], []        # live: [tokens left]
    gaps, held, rows_area = [], 0, 0.0

    def step_ms(rows):
        touched = a.experts * (1 - math.exp(-rows * a.per_row / a.experts)) \
            if a.experts else 0.0
        return a.step_ms + a.row_ms * rows + a.expert_ms * touched

    end = lead + seconds
    while t < end + 1:
        while i < len(reqs) and reqs[i]["due"] <= t:
            waiting.append(reqs[i])
            i += 1
        extra = 0.0
        while waiting and len(live) < a.slots:
            r = waiting.pop(0)
            extra += a.prefill_ms + 1e3 * len(r["prompt"]) / a.prefill_tok_s
            live.append(r["max_new"])
        if not live:
            t = reqs[i]["due"] if i < len(reqs) else end + 1
            continue
        gap = step_ms(len(live)) + extra
        t += gap / 1e3
        if lead <= t < end:
            gaps += [gap] * len(live)
            held += len(live) * (extra > 0)
            rows_area += len(live) * gap / 1e3
        live = [n - 1 for n in live if n > 1]
    g = np.asarray(gaps)
    q = {p: float(np.percentile(g, p)) for p in (50, 93, 95, 97, 99)}
    # how deep p95 lies inside a stretch of equal steps: the percentiles
    # below and above it that stay within 0.3 % of it
    level = np.sort(g)
    near = np.flatnonzero(np.abs(level / q[95] - 1) < 0.003) / len(g) * 100
    return {"seed": seed, "rows": rows_area / seconds, "p50": q[50],
            "p95": q[95], "p99": q[99],
            "flat": (q[97] - q[93]) / q[95],
            "depth": min(95 - near.min(), near.max() - 95),
            "behind": held / len(g),
            "in_slice": sum(a.slice[0] <= r["due"] < a.slice[1]
                            for r in reqs)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mix")
    ap.add_argument("first", type=int)
    ap.add_argument("count", type=int)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--row-ms", type=float, required=True)
    ap.add_argument("--expert-ms", type=float, default=0.0)
    ap.add_argument("--experts", type=float, default=0.0)
    ap.add_argument("--per-row", type=float, default=0.0)
    ap.add_argument("--prefill-ms", type=float, default=40.0)
    ap.add_argument("--prefill-tok-s", type=float, default=20000.0)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--slice", default="33,39")
    ap.add_argument("--rate", type=float, default=None)
    a = ap.parse_args()
    a.slice = tuple(float(x) for x in a.slice.split(","))
    with open(a.mix) as f:
        mix = json.load(f)
    if a.rate is not None:
        mix["rate_per_s"] = a.rate
    out = [simulate(mix, a.first + k, a.seconds, a) for k in range(a.count)]
    for r in out:
        print("{seed} rows {rows:.1f} p50 {p50:.2f} p95 {p95:.2f} p99 "
              "{p99:.2f} flat {flat:.4f} depth {depth:.2f} behind "
              "{behind:.4f} in_slice {in_slice}".format(**r))
    ranked = sorted((r for r in out if r["in_slice"] >= 2),
                    key=lambda r: -r["depth"])
    print("ranked (seed, depth in percentile points, rows):",
          [(r["seed"], round(float(r["depth"]), 2), round(r["rows"], 1))
           for r in ranked[:10]])


if __name__ == "__main__":
    main()
