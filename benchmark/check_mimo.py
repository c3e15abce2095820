"""Where the limits of a ``mimo_v2`` configuration's ``correct`` come
from, and that each can fail (ISSUE 31, step 8). On the chip, at the
configuration's widths. Two phases, one JSON line each at the end:

**dense** (``--seeds`` weight seeds): reference against the program's
dense bfloat16 forward, never the served path: the floors of check (a)
(how far below the float32 reference's maximum the dense forward's
argmax lies, in logit sigmas; the median over the positions of the
distance between the two's logits rows; the distance of the dense
forward's cached keys and values from the reference's) and of check (c)
(the share of (token, expert layer) pairs on which the two choose the
same experts; the program's router on the reference's own router
inputs).

**served** (``--served`` weight seeds): an ``LLMServer`` with the
cell's engine, and the driver's own ``reference_check`` on it, as a run
of the cell makes it: clean on every seed (the floors of check (d):
served against dense, the cached rows, the probe), and on the first
seed once more for each fault of ``faults_mimo.FAULTS`` (or those
``--faults`` names) planted in the SERVED PROGRAM. Exits 1 if a clean
check fails or a planted fault comes out correct.

    python3 benchmark/check_mimo.py --seeds 10 --served 3
    python3 benchmark/check_mimo.py --seeds 10 --router-draws --served 0
    python3 benchmark/check_mimo.py --seeds 0 --served 1 --first-seed 777 \
        --faults ring_one_page_short,window_127
    python3 benchmark/check_mimo.py --rehearse          # CPU, tiny

``--rejudge FILE`` runs nothing: it holds the readings a served phase
kept in ``FILE`` (``chiprun_out/check_mimo.json``) to the limits the
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

import numpy as np  # noqa: E402

CONFIG = "mimo_v25_bf16_ep16"


def dense_phase(cfg, config, sizes, seeds: int, router_draws: bool = False):
    from benchmark import reference, reference_mimo
    from benchmark.drivers import serve_mimo
    from benchmark.drivers.serve_deepseek import (cached_distance,
                                                  row_distance)

    n, new = sizes["prompt_tokens"], sizes["served_tokens"]
    t = n + new - 1
    rows = slice(n - 1, t)
    router = serve_mimo.program_router(cfg)
    out = {"margin_floor_sigma": [], "row_distance_median": [],
           "cached_distance_median": [], "cached_distance_max": [],
           "same_experts": [], "router_alone_share": [],
           "router_alone_weight_off": []}
    for i in range(seeds):
        seed = 1000 + 7919 * i
        t0 = time.perf_counter()
        # the cell draws its routers from the configuration's own seed;
        # with ``router_draws`` every weight seed here gets routers of
        # its own (the first the configuration's), so that the floors
        # of check (c) are read over as many router draws
        drawn = {**config, "weights_router_seed":
                 int(config["weights_router_seed"]) + (i if router_draws
                                                       else 0)}
        params = serve_mimo.seeded_params(cfg, seed, drawn)
        ids = np.random.RandomState(seed).randint(
            0, cfg.vocab_size, t).astype(np.int32)
        logits, chosen, cache = serve_mimo.dense_forward(
            cfg, params, ids, rows)
        picks = logits.argmax(-1)
        routing, ref_rows = [], []
        ref, ref_chosen = reference_mimo.mimo_logits(
            cfg, params, ids, routing=routing, rows=ref_rows)
        dist = np.concatenate([
            cached_distance(
                np.concatenate([k, v], -1).reshape(t, -1),
                np.concatenate([rk, rv], -1).reshape(t, -1))
            for (k, v), (rk, rv) in zip(cache, ref_rows)])
        r_share, w_off = reference_mimo.router_on_reference_inputs(
            router, params, routing)
        got = {"margin_floor_sigma": float(
                   reference.margins(ref[rows], picks).max()),
               "row_distance_median": float(np.median(row_distance(
                   logits, ref[rows]))),
               "cached_distance_median": float(np.median(dist)),
               "cached_distance_max": float(dist.max()),
               "same_experts": float(reference_mimo.same_experts(
                   ref_chosen, chosen).mean()),
               "router_alone_share": r_share,
               "router_alone_weight_off": w_off}
        for k, v in got.items():
            out[k].append(v)
        print(f"# seed {seed}: " + ", ".join(
            f"{k} {v:.5g}" for k, v in got.items())
            + f"; {time.perf_counter() - t0:.1f} s", flush=True)
        del params
    return {"seeds": seeds, "router_draws": seeds if router_draws else 1,
            "prompt_tokens": n, "served_tokens": new, **out}


def served_phase(cfg, config, sizes, engine, seeds: int, faults,
                 first_seed: int = 500):
    """The driver's ``reference_check`` on a fresh server: clean on
    every seed (``first_seed``, then 7,907 apart), and with each fault
    planted on the first."""
    import contextlib
    import gc

    import jax

    from benchmark import faults_mimo
    from benchmark.drivers import serve_mimo
    from bigdl_tpu.llm.models.mimo import MimoForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    def checked(params, seed, fault):
        plant = faults_mimo.planted(fault, cfg) if fault \
            else contextlib.nullcontext()
        t0 = time.perf_counter()
        with plant:
            srv = LLMServer(MimoForCausalLM(cfg, params, max_cache_len=128),
                            **engine).start()
            try:
                out = serve_mimo.reference_check(
                    srv, cfg, params, seed, config, sizes,
                    lambda text: print(f"#   {text}", flush=True))
                out["pass_errors"] = srv.pass_errors
            finally:
                srv.stop()
                del srv
                gc.collect()    # its pools go before the next ones come
        failed = [k for k in "dabc" if not out[k]]
        print(f"# served, seed {seed}, {fault or 'clean'}: failed "
              f"{failed or 'nothing'}; {time.perf_counter() - t0:.1f} s",
              flush=True)
        return {"failed": failed, **out["readings"]}

    clean, planted = [], {}
    for i in range(seeds):
        seed = first_seed + 7907 * i
        params = serve_mimo.seeded_params(cfg, seed, config)
        clean.append({"seed": seed, **checked(params, seed, "")})
        if i == 0:
            for fault in faults:
                planted[fault] = checked(params, seed, fault)
        del params
        jax.clear_caches()
    ok = all(not c["failed"] for c in clean) and \
        all(f["failed"] for f in planted.values())
    return {"served_seeds": seeds, "first_seed": first_seed, "clean": clean,
            "faults_in_the_served_program": planted, "ok": ok}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--served", type=int, default=3)
    ap.add_argument("--faults", default=None,
                    help="comma-separated names; default: all")
    ap.add_argument("--first-seed", type=int, default=500,
                    help="the served phase's first weight seed")
    ap.add_argument("--router-draws", action="store_true",
                    help="dense phase: routers of its own a weight seed")
    ap.add_argument("--keep", default="chiprun_out/check_mimo.json",
                    help="where the readings are kept")
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import bigdl_tpu  # noqa: F401  (places the compile cache)
    import jax

    from benchmark import faults_mimo
    from benchmark import manifest as mf
    from benchmark.drivers import serve_mimo

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
            verdict = serve_mimo.judge(readings, config)
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
    cfg = serve_mimo.model_config(config, reh.get("model", {}))
    sizes = {**config["reference_check"], **reh.get("reference_check", {})}
    engine = {**config["engine"], **reh.get("engine", {})}
    faults = faults_mimo.FAULTS if args.faults is None else \
        tuple(f for f in args.faults.split(",") if f)
    os.makedirs("chiprun_out", exist_ok=True)
    out = {"device": f"{dev.platform} {dev.device_kind}"}

    def keep():     # after each phase: a later one may lose the machine
        with open(args.keep, "w") as f:
            json.dump(out, f)
    if args.seeds:
        out["dense"] = dense_phase(cfg, config, sizes, args.seeds,
                                   args.router_draws)
        print(json.dumps(out["dense"]), flush=True)
        keep()
    rc = 0
    if args.served:
        out["served"] = served_phase(cfg, config, sizes, engine,
                                     args.served, faults, args.first_seed)
        print(json.dumps(out["served"]), flush=True)
        keep()
        rc = 0 if out["served"]["ok"] else 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
