#!/usr/bin/env python
"""Per-step decode latency microbench + pipeline-depth sweep (ISSUE 4).

Drives the LIVE continuous-batching engine (LLMServer — admission,
prefill, paged pool, drain bookkeeping, everything) rather than a bare
compiled step, so what it measures is exactly what a serving deployment
pays per token: device compute PLUS whatever host work the pipeline
fails to hide. Sweeping ``bigdl.llm.pipeline_depth`` makes the async
engine's win legible as the depth-1 → depth-N step-time drop, and the
``host_ms``/``stall_ms`` split (the server's always-on accounting, the
same numbers the ``bigdl_llm_decode_host_seconds`` /
``..._stall_seconds`` histograms carry) shows WHERE the remaining time
goes — a step that is all stall is device-bound; one with host ≈ stall
is scheduling-bound and wants more depth.

Wired into ``bench.py``'s telemetry block like ``tools/chaos_check.py``
(one compact dict under ``telemetry.microbench_decode``; the northstar
summary carries the per-depth step_ms), and runnable standalone:

    python tools/microbench_decode.py                # tiny model sweep
    python tools/microbench_decode.py --depths 1,2,4 --batch 8 \
        --tokens 64 --json
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Iterable, Optional

# runnable both as `python tools/microbench_decode.py` (only the script
# dir is on sys.path then, the package root is not) and as an import
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_microbench(depths: Iterable[int] = (1, 2, 4), batch: int = 4,
                   tokens: int = 32, prompt_len: int = 8,
                   model_size: str = "tiny", page_size: int = 16,
                   warmup_tokens: int = 4,
                   model=None) -> Dict:
    """Decode ``batch`` concurrent requests of ``tokens`` new tokens each
    at every pipeline depth; report per-step wall latency and the
    host/stall attribution. The first (warmup) round per server absorbs
    prefill/decode compiles so the timed window measures steady state —
    compiled paged steps are also shared process-wide, so depths after
    the first reuse the same executables."""
    import numpy as np

    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    if model is None:
        cfg = {"tiny": LlamaConfig.tiny,
               "7b": LlamaConfig.llama2_7b}[model_size]()
        model = LlamaForCausalLM.from_config(cfg, seed=0,
                                             max_cache_len=256)
    rs = np.random.RandomState(0)
    vocab = model.config.vocab_size
    max_seq = min(prompt_len + tokens + warmup_tokens + 2,
                  model.config.max_position_embeddings)
    prompts = [rs.randint(0, vocab, prompt_len).astype(np.int32)
               for _ in range(batch)]
    out: Dict = {"batch": batch, "tokens": tokens,
                 "prompt_len": prompt_len, "model": model_size}
    from bigdl_tpu.observability.sketch import QuantileSketch
    for depth in depths:
        # slo=True makes the engine stamp every token's drain-fence
        # arrival on the request handle (Request.t_tokens) — the exact
        # gaps the bigdl_llm_itl_seconds sketch would observe, read
        # here without touching the global registry
        srv = LLMServer(model, max_batch=batch, max_seq_len=max_seq,
                        page_size=page_size, pipeline_depth=depth,
                        slo=True).start()
        try:
            # warmup: compile prefill buckets + the decode step
            for r in [srv.submit(p, max_new_tokens=warmup_tokens)
                      for p in prompts]:
                r.get(timeout=600)
            steps0, host0, stall0 = (srv.steps, srv.host_seconds,
                                     srv.stall_seconds)
            t0 = time.perf_counter()
            reqs = [srv.submit(p, max_new_tokens=tokens)
                    for p in prompts]
            got = [r.get(timeout=600) for r in reqs]
            wall = time.perf_counter() - t0
            steps = srv.steps - steps0
            # per-request inter-token gaps (ISSUE 14 satellite): the
            # tail is the number mixed-dispatch work is judged on —
            # a mean step_ms hides exactly the spikes that matter
            sk = QuantileSketch()
            for r in reqs:
                for a, b in zip(r.t_tokens, r.t_tokens[1:]):
                    sk.observe(b - a)
            p50, p99 = sk.quantile(0.5), sk.quantile(0.99)
            out[f"depth{depth}"] = {
                "step_ms": round(wall / max(steps, 1) * 1e3, 3),
                "steps": steps,
                "wall_s": round(wall, 3),
                "tokens_per_s": round(sum(len(g) for g in got) / wall, 2),
                "host_ms_per_step": round(
                    (srv.host_seconds - host0) / max(steps, 1) * 1e3, 3),
                "stall_ms_per_step": round(
                    (srv.stall_seconds - stall0) / max(steps, 1) * 1e3,
                    3),
                "itl_p50_ms": (round(p50 * 1e3, 3)
                               if p50 is not None else None),
                "itl_p99_ms": (round(p99 * 1e3, 3)
                               if p99 is not None else None),
            }
        finally:
            srv.stop()
    # best PIPELINED depth vs the synchronous engine — only meaningful
    # (and only emitted) when depth 1 was actually swept; a sweep where
    # every depth is slower than 1 reports < 1.0, not a fake speedup
    base = out.get("depth1", {}).get("step_ms")
    rest = [d["step_ms"] for k, d in out.items()
            if k.startswith("depth") and k != "depth1"]
    if base and rest:
        out["speedup_vs_depth1"] = round(base / min(rest), 3)
    return out


def run_spec_bench(tokens: int = 48, spec_k: int = 8,
                   page_size: int = 8, model=None) -> Dict:
    """Self-speculative decoding on/off sweep (ISSUE 19): batch-1
    greedy decode of a repetitive-suffix workload — the prompt repeats
    a short pattern, so the n-gram proposer's match rate is high and
    the bandwidth win is visible even on the CPU proxy. Reports raw
    tok/s both ways, the accepted-tokens-per-tick the ROADMAP bar is
    stated in (``spec_emitted_total / spec_passes``: how many tokens
    one fence delivered on average), the lifetime draft acceptance
    rate, and the on/off ITL p99. Keyed into bench_regress as
    ``spec.*`` / ``spec_{off,on}.*``."""
    import numpy as np

    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.observability.sketch import QuantileSketch

    if model is None:
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                             max_cache_len=256)
    # seed chosen so the tiny model's greedy continuation itself falls
    # into a short cycle: the proposer drafts from generated history,
    # so what must repeat is the OUTPUT, not just the prompt
    rs = np.random.RandomState(42)
    pattern = rs.randint(0, model.config.vocab_size, 5).astype(np.int32)
    prompt = np.tile(pattern, 6).astype(np.int32)    # 30 repetitive toks
    max_seq = min(len(prompt) + tokens + 8,
                  model.config.max_position_embeddings)
    out: Dict = {"tokens": tokens, "prompt_len": int(len(prompt)),
                 "spec_k": spec_k}
    got = {}
    for mode, sp in (("spec_off", False), ("spec_on", True)):
        srv = LLMServer(model, max_batch=1, max_seq_len=max_seq,
                        page_size=page_size, pipeline_depth=1, slo=True,
                        spec=sp, spec_k=spec_k).start()
        try:
            # full-length warmup: the run is deterministic, so the
            # second pass replays the exact bucket/shape sequence —
            # every spec verify program compiles here, the timed
            # window below is steady state (and the compile-recorder
            # test pins the replay at zero new programs)
            srv.submit(prompt, max_new_tokens=tokens).get(timeout=600)
            t0 = time.perf_counter()
            req = srv.submit(prompt, max_new_tokens=tokens)
            got[mode] = list(map(int, req.get(timeout=600)))
            wall = time.perf_counter() - t0
            sk = QuantileSketch()
            for a, b in zip(req.t_tokens, req.t_tokens[1:]):
                sk.observe(b - a)
            p99 = sk.quantile(0.99)
            out[mode] = {
                "tokens_per_s": round(len(got[mode]) / wall, 2),
                "wall_s": round(wall, 3),
                "itl_p99_ms": (round(p99 * 1e3, 3)
                               if p99 is not None else None),
            }
            if sp:
                out["accepted_tokens_per_tick"] = round(
                    srv.spec_emitted_total / max(srv.spec_passes, 1), 3)
                out["accept_rate"] = round(
                    srv.spec_accepted_total
                    / max(srv.spec_proposed_total, 1), 3)
                out["spec_passes"] = srv.spec_passes
        finally:
            srv.stop()
    # the hard bar: same tokens either way (greedy bit-parity), fewer
    # ticks with speculation
    out["bit_identical"] = got["spec_off"] == got["spec_on"]
    out["tokens_per_s_ratio"] = round(
        out["spec_on"]["tokens_per_s"]
        / max(out["spec_off"]["tokens_per_s"], 1e-9), 3)
    return out


def main(argv) -> int:
    def flag(name: str, default: Optional[str] = None):
        if name in argv:
            return argv[argv.index(name) + 1]
        return default

    if "--spec" in argv:
        out = run_spec_bench(tokens=int(flag("--tokens", "48")),
                             spec_k=int(flag("--spec-k", "8")))
        if "--json" in argv:
            print(json.dumps(out))
            return 0
        print(f"spec decode microbench: tokens={out['tokens']} "
              f"k={out['spec_k']} bit_identical={out['bit_identical']}")
        for mode in ("spec_off", "spec_on"):
            d = out[mode]
            print(f"  {mode:<9} {d['tokens_per_s']:>8.1f} tok/s  "
                  f"itl_p99={d['itl_p99_ms']} ms")
        print(f"  accepted/tick={out['accepted_tokens_per_tick']} "
              f"accept_rate={out['accept_rate']} "
              f"speedup={out['tokens_per_s_ratio']}x")
        return 0
    depths = tuple(int(d) for d in
                   flag("--depths", "1,2,4").split(","))
    out = run_microbench(
        depths=depths,
        batch=int(flag("--batch", "4")),
        tokens=int(flag("--tokens", "32")),
        prompt_len=int(flag("--prompt-len", "8")),
        model_size=flag("--model", "tiny"))
    if "--json" in argv:
        print(json.dumps(out))
        return 0
    print(f"decode microbench: batch={out['batch']} "
          f"tokens={out['tokens']}")
    for k in sorted(k for k in out if k.startswith("depth")):
        d = out[k]
        print(f"  {k:<7} step={d['step_ms']:>8.3f} ms  "
              f"host={d['host_ms_per_step']:>7.3f} ms  "
              f"stall={d['stall_ms_per_step']:>7.3f} ms  "
              f"itl_p99={d['itl_p99_ms']} ms  "
              f"({d['tokens_per_s']:.1f} tok/s)")
    if "speedup_vs_depth1" in out:
        print(f"  speedup vs depth {min(depths)}: "
              f"{out['speedup_vs_depth1']}x")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
