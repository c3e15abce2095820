"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it refuses to run unless JAX finds a TPU with at least the
cell's ``chips`` devices, sets up (weights from ``--seed``, warm-up of the
cell's shapes; all of it is ``setup_s``), measures for ``--seconds``,
checks the outputs, prints ``#`` lines for people and, last, one JSON
line with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` when traced). ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

``--rehearse`` is the CPU rehearsal: the same files and control flow at
the tiny widths the configuration file gives under ``rehearse``; it
prints ``"platform": "cpu"`` and counts, and no device metric value.

How a cell's files are found is in ``benchmark/manifest.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def say(text: str) -> None:
    print(f"# {text}", flush=True)


def device_or_exit(chips: int, rehearse: bool):
    """The devices the cell runs on; exits non-zero, printing no result,
    when JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"rehearsal of a {chips}-chip cell needs XLA_"
                             f"FLAGS=--xla_force_host_platform_device_count"
                             f"={chips}")
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform "
                         f"{devs[0].platform}); nothing was run. "
                         "--rehearse runs the CPU rehearsal")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}; nothing was run")
    return devs[:chips]


class Compiles:
    """Stamps every backend compile (or load from the persistent cache:
    JAX reports both under this event) so that the driver can count the
    programs first used inside the measured window."""

    def __init__(self):
        import jax
        self.stamps = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, _secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.stamps.append(time.perf_counter())

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.stamps if t0 <= t < t1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import manifest as mf
    man = mf.load()
    cell = mf.cell(man, args.workload)
    config = mf.config_of(man, cell)
    mix = mf.traffic_of(cell)
    seconds = args.seconds if args.seconds is not None \
        else float(man["run_seconds"])

    import bigdl_tpu  # noqa: F401  places the compile cache (a fixed
    # path inside the checkout unless JAX_COMPILATION_CACHE_DIR is set)
    import jax
    # jax 0.9 keeps programs that compiled in under a second out of the
    # persistent cache; the engine has some 180 of those, rebuilt in
    # every process. This touches set-up only.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = Compiles()
    devices = device_or_exit(cell["chips"], args.rehearse)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {args.seed}, {seconds:g} s, trace "
        f"{args.trace}{', REHEARSAL' if args.rehearse else ''}; {device}; "
        f"jax {jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}; imports took "
        f"{time.perf_counter() - T_START:.2f} s")

    ctx = {"cell": cell, "config": config, "mix": mix, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace),
           "rehearse": args.rehearse, "t_start": T_START,
           "devices": devices, "device": device, "compiles": compiles,
           "wanted_e2e": [m["name"] for m in mf.metrics_for(
               man, "end_to_end", cell["name"])], "say": say}
    run = mf.driver_of(config).run(ctx)

    say(f"compile cache: {compiles.hits} hit(s), {compiles.misses} "
        f"miss(es); programs built or loaded in the process "
        f"{len(compiles.stamps)}, inside the window "
        f"{run['counters'].get('compiles_in_window')}")
    if args.trace:
        wanted = mf.metrics_for(man, "per_layer", cell["name"])
        values = {}
        for m in wanted:
            v = mf.reader_of(m["name"]).read(run, m["name"])
            if v is not None:
                values[m["name"]] = v
    else:
        wanted = mf.metrics_for(man, "end_to_end", cell["name"])
        values = {m["name"]: run["e2e"][m["name"]] for m in wanted}
    units = {m["name"]: m["unit"] for m in wanted}
    if args.rehearse:
        # a CPU run never prints a number under a device metric's name
        metrics = {k: {"value": None, "unit": units[k]} for k in values}
        say("rehearsal values (CPU, not device numbers): " + ", ".join(
            f"{k}={v:.4g}" for k, v in values.items()))
    else:
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in values.items()}
    # the fullest chip's peak as the runtime counts it, and nothing else
    device["memory_peak_bytes"] = 0 if args.rehearse else max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices)
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics,
            "device": device}
    if args.trace and run.get("trace") is not None:
        from benchmark import trace_reduce
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(run["trace"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
