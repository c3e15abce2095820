"""A cell's knee: one run of ``benchmark/run.py`` a rate, each in a
process of its own, the cell's traffic file rewritten with that rate for
the run and put back after it (run it on the chip's disposable copy).

    python3 tools/sweep_rate.py <cell> <seconds> <seed> <rate> ...
    python3 tools/sweep_rate.py <cell> <seconds> <seed> <rate> ... \\
        --bisect [--runs <seed>,<seed>,...]

The ``#`` lines of every run (and the last lines of its stderr where it
exits non-zero) go to ``chiprun_out/sweep_<cell>.txt``, the result lines
to stdout. With ``--bisect`` the rates (sorted) are tried from the
middle, up after a rate that holds and down after one that does not,
until the knee, the highest rate that holds, is bracketed: a rate holds
where the run exits 0 with no request due in the window failed (each
done, inside the drain allowance) and the requests waiting for a first
token grow by at most one from the window's first quarter to its last.
``--runs`` then runs the cell at 4/5 of the knee once a seed, the
traffic file holding that rate until the last of them ends. This
process never touches JAX."""

import argparse
import ast
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_at(cell, seconds, seed, rate, path, kept, log):
    """One run of the cell at ``rate``: (exit code, stdout, result line
    or None)."""
    mix = json.loads(kept)
    mix["rate_per_s"] = float(rate)
    with open(path, "w") as f:
        json.dump(mix, f, indent=1)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True)
    out = done.stdout
    # a run that exits without its result line says why here
    err = "" if done.returncode == 0 else "\n".join(
        done.stderr.strip().splitlines()[-12:])
    log.write(f"=== rate {rate} seed {seed}\n{out}\n{err}\n")
    log.flush()
    keep = [ln for ln in out.splitlines() if any(
        k in ln for k in ("itl ms", "ttft ms", "waiting for a first",
                          "tokens seen", "state rows", "reference: (a)"))]
    last = (out.strip().splitlines() or [""])[-1]
    print(f"=== rate {rate} seed {seed} rc {done.returncode}\n"
          + "\n".join(keep) + "\n" + last + ("\n" + err if err else ""),
          flush=True)
    try:
        line = json.loads(last)
    except ValueError:
        line = None
    return done.returncode, out, line


def holds(rc, out, line) -> bool:
    if rc != 0 or not line or line.get("failed") != 0 \
            or not line.get("attempted"):
        return False
    for ln in out.splitlines():
        if "waiting for a first token, mean per quarter" in ln:
            q = ast.literal_eval(ln.split("quarter ", 1)[1].split("]")[0]
                                 + "]")
            return q[-1] - q[0] <= 1.0
    return False


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seconds")
    ap.add_argument("seed")
    ap.add_argument("rates", nargs="+", type=float)
    ap.add_argument("--bisect", action="store_true")
    ap.add_argument("--runs", default="")
    a = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mix_name = next(w["traffic"] for w in json.load(f)["workloads"]
                        if w["name"] == a.cell)
    path = os.path.join(ROOT, "benchmark", "traffic", mix_name + ".json")
    with open(path) as f:
        kept = f.read()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", f"sweep_{a.cell}.txt"), "a")
    try:
        if not a.bisect:
            for rate in a.rates:
                run_at(a.cell, a.seconds, a.seed, rate, path, kept, log)
            return
        rates = sorted(a.rates)
        lo, hi = 0, len(rates) - 1          # untried rates lie in lo..hi
        knee = None
        while lo <= hi:
            mid = (lo + hi) // 2
            ok = holds(*run_at(a.cell, a.seconds, a.seed, rates[mid], path,
                               kept, log))
            print(f"# rate {rates[mid]} holds: {ok}", flush=True)
            if ok:
                knee, lo = rates[mid], mid + 1
            else:
                hi = mid - 1
        print(f"# knee {knee}", flush=True)
        if knee is None or not a.runs:
            return
        rate = round(0.8 * knee, 6)
        print(f"# runs at 4/5 of the knee: {rate} requests/s", flush=True)
        for seed in a.runs.split(","):
            run_at(a.cell, a.seconds, seed, rate, path, kept, log)
    finally:
        with open(path, "w") as f:
            f.write(kept)


if __name__ == "__main__":
    main()
