"""Where the limits of a ``brumby`` configuration's ``correct`` come
from, and that each can fail (ISSUE 33, step 7). On the chip, at the
configuration's widths: an ``LLMServer`` with the cell's engine and the
driver's own check on it (``serve_brumby.serve_for_check`` then
``compare_served``, as a run of the cell makes them): clean on
``--served`` weight seeds (the floors), and on the first seed once more
for each fault of ``faults_brumby.FAULTS`` (or those ``--faults``
names) planted in the SERVED PROGRAM. Exits 1 if a clean check fails or
a planted fault comes out correct.

    python3 benchmark/check_brumby.py --served 3
    python3 benchmark/check_brumby.py --served 1 --faults state_bf16
    python3 benchmark/check_brumby.py --rehearse          # CPU, tiny

``--rejudge FILE`` runs nothing: it holds the readings a run kept in
``FILE`` (``chiprun_out/check_brumby.json``) to the limits the
configuration file states NOW, with the driver's own ``judge``, and
exits 1 likewise; for limits that were chosen from those readings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG = "brumby14b_bf16_pp5"


def served_phase(cfg, config, sizes, engine, seeds: int, faults,
                 first_seed: int = 500, keep=None):
    """The driver's check on a fresh server: clean on every seed
    (``first_seed``, then 7,907 apart), and with each fault planted on
    the first."""
    import contextlib
    import gc

    import jax

    from benchmark import faults_brumby
    from benchmark.drivers import serve_brumby
    from bigdl_tpu.llm.models.brumby import BrumbyForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    def say(text):
        print(f"#   {text}", flush=True)

    def checked(params, seed, fault):
        plant = faults_brumby.planted(fault, cfg) if fault \
            else contextlib.nullcontext()
        t0 = time.perf_counter()
        with plant:
            srv = LLMServer(BrumbyForCausalLM(cfg, params, max_cache_len=128),
                            **engine).start()
            try:
                took = serve_brumby.serve_for_check(srv, cfg, seed, sizes)
                errors = srv.pass_errors
            finally:
                srv.stop()
                del srv
                gc.collect()    # its state goes before the reference comes
            out = serve_brumby.compare_served(cfg, params, took, config, say)
        failed = [k for k in "dabc" if not out[k]]
        print(f"# served, seed {seed}, {fault or 'clean'}: failed "
              f"{failed or 'nothing'}; pass errors {errors}; "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        return {"failed": failed, "pass_errors": errors, **out["readings"]}

    out = {"served_seeds": seeds, "first_seed": first_seed, "clean": [],
           "faults_in_the_served_program": {}}
    for i in range(seeds):
        seed = first_seed + 7907 * i
        params = serve_brumby.seeded_params(cfg, seed, config)
        out["clean"].append({"seed": seed, **checked(params, seed, "")})
        if i == 0:
            for fault in faults:
                out["faults_in_the_served_program"][fault] = \
                    checked(params, seed, fault)
                if keep:
                    keep(out)
        del params
        jax.clear_caches()
        if keep:
            keep(out)
    out["ok"] = all(not c["failed"] for c in out["clean"]) and all(
        f["failed"] for f in out["faults_in_the_served_program"].values())
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--served", type=int, default=3)
    ap.add_argument("--faults", default=None,
                    help="comma-separated names; default: all")
    ap.add_argument("--first-seed", type=int, default=500)
    ap.add_argument("--keep", default="chiprun_out/check_brumby.json",
                    help="where the readings are kept")
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import bigdl_tpu  # noqa: F401  (places the compile cache)
    import jax

    from benchmark import faults_brumby
    from benchmark import manifest as mf
    from benchmark.drivers import serve_brumby

    with open(os.path.join(mf.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    if args.rejudge:
        with open(args.rejudge) as f:
            kept = json.load(f)["served"]
        cases = [(f"clean {i}", c, False)
                 for i, c in enumerate(kept["clean"])] + [
            (f, c, True) for f, c in
            kept["faults_in_the_served_program"].items()]
        ok = True
        for name, readings, planted in cases:
            verdict = serve_brumby.judge(readings, config)
            failed = [k for k in "dabc" if not verdict[k]]
            ok &= bool(failed) == planted
            print(f"{name}: failed {failed or 'nothing'}")
        return 0 if ok else 1
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU; use --rehearse for the CPU rehearsal", file=sys.stderr)
        return 3
    reh = config["rehearse"] if args.rehearse else {}
    cfg = serve_brumby.model_config(config, reh.get("model", {}))
    sizes = {**config["reference_check"], **reh.get("reference_check", {})}
    engine = {**config["engine"], **reh.get("engine", {})}
    faults = faults_brumby.FAULTS if args.faults is None else \
        tuple(f for f in args.faults.split(",") if f)
    os.makedirs("chiprun_out", exist_ok=True)
    out = {"device": f"{dev.platform} {dev.device_kind}"}

    def keep(served):   # after each check: a later one may lose the machine
        out["served"] = served
        with open(args.keep, "w") as f:
            json.dump(out, f)
    served = served_phase(cfg, config, sizes, engine, args.served, faults,
                          args.first_seed, keep)
    keep(served)
    print(json.dumps(served), flush=True)
    return 0 if served["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
