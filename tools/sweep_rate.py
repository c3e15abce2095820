"""A cell's knee: one run of ``benchmark/run.py`` a rate, each in a
process of its own, the cell's traffic file rewritten with that rate for
the run and put back after it (run it on the chip's disposable copy).
``python3 tools/sweep_rate.py <cell> <seconds> <seed> <rate> ...``; the
``#`` lines of every run go to ``chiprun_out/sweep_<cell>.txt``, the
result lines to stdout. This process never touches JAX."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    cell, seconds, seed, *rates = sys.argv[1:]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mix_name = next(w["traffic"] for w in json.load(f)["workloads"]
                        if w["name"] == cell)
    path = os.path.join(ROOT, "benchmark", "traffic", mix_name + ".json")
    with open(path) as f:
        kept = f.read()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", f"sweep_{cell}.txt"), "a")
    try:
        for rate in rates:
            mix = json.loads(kept)
            mix["rate_per_s"] = float(rate)
            with open(path, "w") as f:
                json.dump(mix, f, indent=1)
            out = subprocess.run(
                [sys.executable, "benchmark/run.py", "--workload", cell,
                 "--seed", seed, "--seconds", seconds, "--trace", "0"],
                cwd=ROOT, capture_output=True, text=True).stdout
            log.write(f"=== rate {rate}\n{out}\n")
            log.flush()
            keep = [ln for ln in out.splitlines() if any(
                k in ln for k in ("itl ms", "waiting for a first",
                                  "tokens seen", "state rows"))]
            print(f"=== rate {rate}\n" + "\n".join(keep) + "\n"
                  + out.strip().splitlines()[-1], flush=True)
    finally:
        with open(path, "w") as f:
            f.write(kept)


if __name__ == "__main__":
    main()
