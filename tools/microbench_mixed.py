#!/usr/bin/env python
"""Mixed-load microbench (ISSUE 14 satellite): inter-token latency of a
steady decode stream while a LONG prompt is admitted mid-run, unified
dispatch off vs on.

The TTFT-vs-ITL tradeoff this PR deletes: with the split engine, a long
admission prefills the WHOLE prompt in one pass, so every in-flight
decode stalls for that pass — the stream's p99 inter-token gap spikes
to the full prefill wall. With ``bigdl.llm.mixed.enabled`` the prompt
is fed in ``bigdl.llm.prefill.chunk_tokens`` page-aligned chunks fused
into the decode passes, so the worst gap is bounded by one chunk.

What it reports, per mode (``mixed_off`` / ``mixed_on``):

- ``itl_p50/p95/p99_ms``: percentiles of the STREAM requests' token
  gaps, computed from the engine's per-token drain stamps
  (``Request.t_tokens``, recorded by the SLO account — the exact
  fence-arrival clocks ``bigdl_llm_itl_seconds`` observes) through a
  PR 12 :class:`~bigdl_tpu.observability.sketch.QuantileSketch`;
- ``ttft_ms``: the long prompt's submit→first-token wall — chunking
  trades a bounded TTFT increase for the deleted ITL spike;
- ``chunks`` / ``mixed_passes``: the engine's always-on tallies (the
  on-mode run must actually have chunked).

Wired into ``bench.py``'s telemetry block (``telemetry.mixed_dispatch``),
the compact northstar line and ``tools/bench_regress.py``
(``mixed.itl_p99_ms`` / ``mixed.ttft_ms`` + the off/on pairs).
Standalone::

    python tools/microbench_mixed.py                    # small sizes
    python tools/microbench_mixed.py --prompt-len 2048 --json
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, Optional

# runnable both as `python tools/microbench_mixed.py` and as an import
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _pcts(sketch) -> Dict[str, float]:
    out = {}
    for q, key in ((0.5, "itl_p50_ms"), (0.95, "itl_p95_ms"),
                   (0.99, "itl_p99_ms")):
        v = sketch.quantile(q)
        out[key] = round(v * 1e3, 3) if v is not None else None
    return out


def run_mixed_bench(batch: int = 4, stream_tokens: int = 40,
                    prompt_len: int = 256, chunk_tokens: int = 32,
                    page_size: int = 16, pipeline_depth: int = 2,
                    model=None) -> Dict:
    """Decode ``batch`` steady streams; once every stream has produced
    a few tokens, admit ONE ``prompt_len``-token prompt (the 2–4k-token
    case scaled to the model at hand) and keep streaming. Both modes
    run the ragged in-place prefill (chunking requires it; forcing it
    in the off mode isolates the DISPATCH change, not the PR 8 kernel)
    and a warmup round absorbs every compile."""
    import time

    import numpy as np

    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.observability.sketch import QuantileSketch

    if model is None:
        cfg0 = LlamaConfig.tiny()
        if cfg0.max_position_embeddings < prompt_len + 24:
            # the 2–4k-token standalone case: widen the tiny config's
            # position range so the admission is genuinely long
            import dataclasses
            cfg0 = dataclasses.replace(
                cfg0, max_position_embeddings=prompt_len + 24)
        model = LlamaForCausalLM.from_config(
            cfg0, seed=0, max_cache_len=prompt_len + 64)
    cfg = model.config
    prompt_len = min(prompt_len, cfg.max_position_embeddings - 16)
    rs = np.random.RandomState(0)
    stream_prompts = [rs.randint(0, cfg.vocab_size, 8).astype(np.int32)
                      for _ in range(batch)]
    long_prompt = rs.randint(0, cfg.vocab_size,
                             prompt_len).astype(np.int32)
    max_seq = min(prompt_len + 24, cfg.max_position_embeddings)
    per_stream = -(-(8 + stream_tokens + 4) // page_size)
    num_pages = (1 + batch * per_stream
                 + -(-(prompt_len + 24) // page_size) + 4)
    out: Dict = {"batch": batch, "stream_tokens": stream_tokens,
                 "prompt_len": int(prompt_len),
                 "chunk_tokens": chunk_tokens, "page_size": page_size}
    for mode, mkey in ((False, "mixed_off"), (True, "mixed_on")):
        srv = LLMServer(model, max_batch=batch + 1, max_seq_len=max_seq,
                        page_size=page_size, num_pages=num_pages,
                        pipeline_depth=pipeline_depth, slo=True,
                        mixed=mode, chunk_tokens=chunk_tokens).start()
        try:
            # warmup: stream + long-prompt buckets (and, mode on, the
            # mixed/chunk programs) all compile outside the timed run
            warm = [srv.submit(p, max_new_tokens=4)
                    for p in stream_prompts]
            warm.append(srv.submit(long_prompt, max_new_tokens=2))
            for r in warm:
                r.get(timeout=1200)
            chunks0 = srv.prefill_chunks_total
            streams = [srv.submit(p, max_new_tokens=stream_tokens)
                       for p in stream_prompts]
            # admit the long prompt once every stream is decoding; a
            # failed stream (done with error, tokens frozen) or a
            # wedged engine must fail the bench, not hang it — bench.py
            # only catches exceptions
            deadline = time.perf_counter() + 600
            while not all(len(r.tokens) >= 2 or r.done.is_set()
                          for r in streams):
                if time.perf_counter() > deadline:
                    raise RuntimeError(
                        "mixed bench: streams never started decoding")
                time.sleep(0.001)
            big = srv.submit(long_prompt, max_new_tokens=4)
            big.get(timeout=1200)
            for r in streams:
                r.get(timeout=1200)
            sk = QuantileSketch()
            for r in streams:
                for a, b in zip(r.t_tokens, r.t_tokens[1:]):
                    sk.observe(b - a)
            entry = _pcts(sk)
            entry["ttft_ms"] = round(
                (big.t_first_token - big.t_submit) * 1e3, 3)
            entry["itl_samples"] = sk.count
            entry["chunks"] = srv.prefill_chunks_total - chunks0
            entry["mixed_passes"] = srv.mixed_passes
            out[mkey] = entry
        finally:
            srv.stop()
    if out["mixed_on"]["chunks"] == 0:
        out["warning"] = ("unified mode never chunked — prompt_len vs "
                          "chunk_tokens leaves nothing to interleave")
    p99_off = out["mixed_off"].get("itl_p99_ms")
    p99_on = out["mixed_on"].get("itl_p99_ms")
    if p99_off and p99_on:
        out["itl_p99_ratio_off_on"] = round(p99_off / p99_on, 3)
    return out


def main(argv) -> int:
    def flag(name: str, default: Optional[str] = None):
        if name in argv:
            return argv[argv.index(name) + 1]
        return default

    out = run_mixed_bench(
        batch=int(flag("--batch", "4")),
        stream_tokens=int(flag("--stream-tokens", "40")),
        prompt_len=int(flag("--prompt-len", "256")),
        chunk_tokens=int(flag("--chunk-tokens", "32")),
        page_size=int(flag("--page-size", "16")),
        pipeline_depth=int(flag("--depth", "2")))
    if "--json" in argv:
        print(json.dumps(out))
        return 0
    print(f"mixed-load microbench: {out['batch']} streams + one "
          f"{out['prompt_len']}-token admission "
          f"(chunk={out['chunk_tokens']})")
    for mkey in ("mixed_off", "mixed_on"):
        d = out[mkey]
        print(f"  {mkey:<9} itl p50={d['itl_p50_ms']} "
              f"p95={d['itl_p95_ms']} p99={d['itl_p99_ms']} ms  "
              f"ttft={d['ttft_ms']} ms  chunks={d['chunks']}")
    if "itl_p99_ratio_off_on" in out:
        print(f"  itl p99 off/on: {out['itl_p99_ratio_off_on']}x")
    if "warning" in out:
        print(f"  WARNING: {out['warning']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
