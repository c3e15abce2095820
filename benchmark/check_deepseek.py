"""Where the limits of a ``deepseek_v3`` configuration's ``correct`` come
from, and that each can fail (ISSUE 27, Tentpole 4). On the chip, at the
configuration's widths. Two phases, one JSON line each at the end:

**dense** (``--seeds`` weight seeds): reference against the program's
dense bfloat16 forward, never the served path:

- the floors of check (a): how far below the float32 reference's maximum
  the dense forward's argmax lies, in logit sigmas, and the median over
  the positions of the distance between the two's logits rows
  (``serve_deepseek.row_distance``);
- the floor of check (c): the share of (token, expert layer) pairs on
  which the two choose the same experts, and the same for the program's
  router on the reference's own router inputs, with the largest relative
  difference of a weight;
- for each fault of ``reference_deepseek.FAULTS`` put in the REFERENCE,
  the same numbers over ``--fault-seeds`` seeds (the comparison is
  symmetric; cheap, so it is made on several seeds).

**served** (``--served`` weight seeds): an ``LLMServer`` with the cell's
engine, and the driver's own ``reference_check`` on it, as a run of the
cell makes it: clean on every seed (the floor of check (d), served
against dense), and on the first seed once more for each fault of
``faults_deepseek.FAULTS`` planted in the SERVED PROGRAM. Exits 1 if a
clean check fails or a planted fault comes out correct.

    python3 benchmark/check_deepseek.py --seeds 20 --fault-seeds 2 --served 3
    python3 benchmark/check_deepseek.py --rehearse          # CPU, tiny

``--back`` overrides the file's ``weights_back_gain`` (to choose it).
``--rejudge FILE`` runs nothing: it holds the readings a served phase
kept in ``FILE`` (``chiprun_out/check_deepseek.json``) to the limits the
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

CONFIG = "kanana2_30b_a3b_bf16"


def dense_phase(cfg, config, sizes, back, seeds: int, fault_seeds: int):
    from benchmark import reference, reference_deepseek, weights_deepseek
    from benchmark.drivers import serve_deepseek

    n, new = sizes["prompt_tokens"], sizes["served_tokens"]
    t = n + new - 1
    rows = slice(n - 1, t)
    router = serve_deepseek.program_router(cfg)
    floors, dists, shares, routers = [], [], [], []
    faults = {f: [] for f in reference_deepseek.FAULTS}
    for i in range(seeds):
        seed = 1000 + 7919 * i
        t0 = time.perf_counter()
        params = weights_deepseek.seeded_bf16_params(cfg, seed, back)
        ids = np.random.RandomState(seed).randint(
            0, cfg.vocab_size, t).astype(np.int32)
        logits, chosen, _ = serve_deepseek.dense_forward(cfg, params, ids)
        picks = logits[rows].argmax(-1)
        routing = []
        ref, ref_chosen = reference_deepseek.deepseek_logits(
            cfg, params, ids, routing=routing)
        floor = float(reference.margins(ref[rows], picks).max())
        dist = float(np.median(serve_deepseek.row_distance(
            logits[rows], ref[rows])))
        share = float(reference_deepseek.same_experts(
            ref_chosen, chosen).mean())
        r_share, w_off = reference_deepseek.router_on_reference_inputs(
            router, params, routing)
        floors.append(floor)
        dists.append(dist)
        shares.append(share)
        routers.append((r_share, w_off))
        line = (f"# seed {seed}: floor {floor:.4f} sigma, rows {dist:.4f} "
                f"apart, same experts {share:.4f}, router alone "
                f"{r_share:.5f} / {w_off:.1e}")
        if i < fault_seeds:
            for fault in reference_deepseek.FAULTS:
                routing = []
                bad, bad_chosen = reference_deepseek.deepseek_logits(
                    cfg, params, ids, fault=fault, routing=routing)
                m = float(reference.margins(bad[rows], picks).max())
                d = float(np.median(serve_deepseek.row_distance(
                    logits[rows], bad[rows])))
                s = float(reference_deepseek.same_experts(
                    bad_chosen, chosen).mean())
                r, w = reference_deepseek.router_on_reference_inputs(
                    router, params, routing)
                faults[fault].append((m, s, r, w, d))
                line += f"; {fault} {m:.3f}/{d:.4f}/{s:.3f}/{r:.4f}/{w:.1e}"
        print(line + f"; {time.perf_counter() - t0:.1f} s", flush=True)
        del params
    return {"seeds": seeds, "prompt_tokens": n, "served_tokens": new,
            "weights_back_gain": back,
            "margin_floor_sigma_max": max(floors),
            "margin_floor_sigma_all": floors,
            "row_distance_median_max": max(dists),
            "row_distance_median_all": dists,
            "same_experts_min": min(shares), "same_experts_all": shares,
            "router_alone_share_min": min(r for r, _ in routers),
            "router_alone_weight_off_max": max(w for _, w in routers),
            "faults_in_the_reference": {
                f: {"margin_sigma": [x[0] for x in v],
                    "row_distance_median": [x[4] for x in v],
                    "same_experts": [x[1] for x in v],
                    "router_alone_share": [x[2] for x in v],
                    "router_alone_weight_off": [x[3] for x in v]}
                for f, v in faults.items()}}


def served_phase(cfg, config, sizes, engine, back, seeds: int):
    """The driver's ``reference_check`` on a fresh server: clean on
    every seed, and with each fault planted on the first."""
    import contextlib
    import gc

    import jax

    from benchmark import faults_deepseek, weights_deepseek
    from benchmark.drivers import serve_deepseek
    from bigdl_tpu.llm.models.deepseek import DeepseekForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    def checked(params, seed, fault):
        plant = faults_deepseek.planted(fault, cfg) if fault \
            else contextlib.nullcontext()
        t0 = time.perf_counter()
        with plant:
            srv = LLMServer(DeepseekForCausalLM(cfg, params,
                                                max_cache_len=128),
                            **engine).start()
            try:
                out = serve_deepseek.reference_check(
                    srv, cfg, params, seed, config, sizes,
                    lambda text: print(f"#   {text}", flush=True))
                out["pass_errors"] = srv.pass_errors
            finally:
                srv.stop()
                del srv
                gc.collect()    # its pool goes before the next one comes
        failed = [k for k in "dabc" if not out[k]]
        print(f"# served, seed {seed}, {fault or 'clean'}: failed "
              f"{failed or 'nothing'}; {out['readings']}; "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        return {"failed": failed, **out["readings"]}

    clean, faults = [], {}
    for i in range(seeds):
        seed = 500 + 7907 * i
        params = weights_deepseek.seeded_bf16_params(cfg, seed, back)
        clean.append(checked(params, seed, ""))
        if i == 0:
            for fault in faults_deepseek.FAULTS:
                faults[fault] = checked(params, seed, fault)
        del params
        jax.clear_caches()
    ok = all(not c["failed"] for c in clean) and \
        all(f["failed"] for f in faults.values())
    return {"served_seeds": seeds, "clean": clean,
            "faults_in_the_served_program": faults, "ok": ok}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=20)
    ap.add_argument("--fault-seeds", type=int, default=2)
    ap.add_argument("--served", type=int, default=3)
    ap.add_argument("--back", type=float, default=None)
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import bigdl_tpu  # noqa: F401  (places the compile cache)
    import jax

    from benchmark import manifest as mf
    from benchmark.drivers import serve_deepseek

    if args.rejudge:
        with open(os.path.join(mf.HERE, "configs", CONFIG + ".json")) as f:
            config = json.load(f)
        with open(args.rejudge) as f:
            kept = json.load(f)["served"]
        cases = [(f"clean {i}", c, False)
                 for i, c in enumerate(kept["clean"])] + [
            (f, c, True) for f, c in
            kept["faults_in_the_served_program"].items()]
        ok = True
        for name, readings, planted in cases:
            verdict = serve_deepseek.judge(readings, config)
            failed = [k for k in "dabc" if not verdict[k]]
            ok &= bool(failed) == planted
            print(f"{name}: failed {failed or 'nothing'}")
        return 0 if ok else 1
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU; use --rehearse for the CPU rehearsal", file=sys.stderr)
        return 3
    with open(os.path.join(mf.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    reh = config["rehearse"] if args.rehearse else {}
    cfg = serve_deepseek.model_config(config, reh.get("model", {}))
    sizes = {**config["reference_check"], **reh.get("reference_check", {})}
    engine = {**config["engine"], **reh.get("engine", {})}
    back = float(config["weights_back_gain"]) if args.back is None \
        else args.back
    os.makedirs("chiprun_out", exist_ok=True)
    out = {"device": f"{dev.platform} {dev.device_kind}"}

    def keep():     # after each phase: a later one may lose the machine
        with open("chiprun_out/check_deepseek.json", "w") as f:
            json.dump(out, f)
    if args.seeds:
        out["dense"] = dense_phase(cfg, config, sizes, back, args.seeds,
                                   args.fault_seeds)
        print(json.dumps(out["dense"]), flush=True)
        keep()
    rc = 0
    if args.served:
        out["served"] = served_phase(cfg, config, sizes, engine, back,
                                     args.served)
        print(json.dumps(out["served"]), flush=True)
        keep()
        rc = 0 if out["served"]["ok"] else 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
