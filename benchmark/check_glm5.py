"""That each limit of a ``glm_moe_dsa`` configuration's ``correct`` can
fail, and where its floors lie. On the chip, at the
configuration's widths: an ``LLMServer`` with the cell's engine and the
cell's own check on it (``serve_glm5.serve_for_check`` then
``compare_served``, as a run of the cell makes them, with the request
the check follows in the window served here instead, beside
``company`` rows, at its own length): clean on ``--served`` weight
seeds (the floors; every run of the cell reads them too, on its own
seed), and on the first seed once more for each fault of
``faults_glm5.FAULTS`` (or those ``--faults`` names) planted in the
SERVED PROGRAM. Exits 1 if a clean check fails or a planted fault comes
out correct.

    python3 benchmark/check_glm5.py --served 2
    python3 benchmark/check_glm5.py --served 0 --faults top2047
    python3 benchmark/check_glm5.py --rehearse          # CPU, tiny

``--rejudge FILE`` runs nothing: it holds the readings a run kept in
``FILE`` (``chiprun_out/check_glm5.json``) to the limits the
configuration file states NOW, with ``serve_glm5.judge``, and
exits 1 likewise; for limits that were chosen from those readings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG = "glm5_bf16_ep16"
CELL = "glm5_longctx_steady"


def served_phase(cfg, config, sizes, engine, seeds: int, faults,
                 first_seed: int = 500, keep=None, *, mix, seconds, scale):
    """The cell's check on a fresh server: clean on ``seeds`` seeds
    (``first_seed``, then 7,907 apart), and with each fault planted on
    the first seed's weights. The request it follows is the one a run
    of the cell on that seed would (``serve_glm5.check_target``)."""
    import contextlib
    import gc

    from benchmark import faults_glm5
    from benchmark.drivers import serve_glm5 as drv
    from bigdl_tpu.llm.models.deepseek import DeepseekForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    def say(text):
        print(f"#   {text}", flush=True)

    def checked(params, seed, fault):
        plant = faults_glm5.planted(fault, cfg) if fault \
            else contextlib.nullcontext()
        t0 = time.perf_counter()
        with plant:
            srv = LLMServer(
                DeepseekForCausalLM(cfg, params, max_cache_len=128),
                **engine).start()
            try:
                took = drv.serve_for_check(
                    srv, cfg, seed, sizes, mix=mix, scale=scale,
                    target=drv.check_target(mix, seed, seconds,
                                            cfg.vocab_size, scale, sizes))
                errors = srv.pass_errors
            finally:
                srv.stop()
                del srv
                gc.collect()    # its state goes before the reference comes
            out = drv.compare_served(cfg, params, took, config, say)
        verdict = drv.judge(out["readings"], config)
        failed = [k for k in drv.VERDICTS if not verdict[k]]
        print(f"# served, seed {seed}, {fault or 'clean'}: failed "
              f"{failed or 'nothing'}; pass errors {errors}; "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        return {"failed": failed, "pass_errors": errors, **out["readings"]}

    out = {"served_seeds": seeds, "first_seed": first_seed, "clean": [],
           "faults_in_the_served_program": {}}
    for i in range(max(seeds, 1 if faults else 0)):
        seed = first_seed + 7907 * i
        params = drv.seeded_params(cfg, seed, config)
        if i < seeds:
            out["clean"].append({"seed": seed, **checked(params, seed, "")})
        if i == 0:
            for fault in faults:
                out["faults_in_the_served_program"][fault] = \
                    checked(params, seed, fault)
                if keep:
                    keep(out)
        del params
        if keep:
            keep(out)
    out["ok"] = all(not c["failed"] for c in out["clean"]) and all(
        f["failed"] for f in out["faults_in_the_served_program"].values())
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--served", type=int, default=1)
    ap.add_argument("--faults", default=None,
                    help="comma-separated names; default: all")
    ap.add_argument("--first-seed", type=int, default=500)
    ap.add_argument("--keep", default="chiprun_out/check_glm5.json",
                    help="where the readings are kept")
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import bigdl_tpu  # noqa: F401  (places the compile cache)
    import jax

    from benchmark import faults_glm5
    from benchmark import manifest as mf
    from benchmark.drivers import serve_glm5 as drv

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
            verdict = drv.judge(readings, config)
            failed = [k for k in drv.VERDICTS if not verdict[k]]
            ok &= bool(failed) == planted
            print(f"{name}: failed {failed or 'nothing'}")
        return 0 if ok else 1
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU; use --rehearse for the CPU rehearsal", file=sys.stderr)
        return 3
    reh = config["rehearse"] if args.rehearse else {}
    cfg = drv.model_config(config, reh.get("model", {}))
    sizes = drv.check_sizes(config, args.rehearse)
    engine = {**config["engine"], **reh.get("engine", {})}
    man = mf.load()
    mix = mf.traffic_of(mf.cell(man, CELL))
    faults = faults_glm5.FAULTS if args.faults is None else \
        tuple(f for f in args.faults.split(",") if f)
    os.makedirs("chiprun_out", exist_ok=True)
    out = {"device": f"{dev.platform} {dev.device_kind}"}

    def keep(served):   # after each check: a later one may lose the machine
        out["served"] = served
        with open(args.keep, "w") as f:
            json.dump(out, f)
    served = served_phase(cfg, config, sizes, engine, args.served, faults,
                          args.first_seed, keep, mix=mix,
                          seconds=float(man["run_seconds"]),
                          scale=float(reh.get("length_scale", 1.0)))
    keep(served)
    print(json.dumps(served), flush=True)
    return 0 if served["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
