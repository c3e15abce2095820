"""Build step 1 of ISSUE 24: is what ``LLMServer`` serves correct on the chip?

Serves seeded prompts for ``--new`` (16) tokens through a fresh
``LLMServer(max_batch=16, max_seq_len=2048)`` over the benchmark's seeded
sym_int4 Mistral-7B (``weights.py``), then scores every served token, teacher-forced on the served
ids, against

(a) the benchmark's plain float32 reference (``reference.llama_logits``),
(b) the program's dense forward ``LlamaForCausalLM(...)(ids)`` (INT4
    kernel + XLA attention over a contiguous cache, no page pool),

as a per-position margin: how many of that row's logit standard
deviations the served token lies below the row's maximum. It also prints
the (a)-(b) distance, which is the bf16-against-float32 noise floor the
benchmark's tolerance is set from.

    python3 benchmark/check_served.py            # weights 0, prompts of 64, 63, 65
    python3 benchmark/check_served.py --seed N [N ...]
    python3 benchmark/check_served.py --rehearse # CPU, LlamaConfig.tiny()

``--seed N`` repeats, on a fresh server, exactly the check a run of
``run.py --seed N`` makes after its window (the run's weights and its
64-token prompt; the first 16 tokens are the run's): it tells a fault that follows the data from one that
follows what the server did before, and which of (a), (b) and the served
path disagree.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

def check(cfg, seq: int, weight_seed: int, prompts, tol: float,
          new: int) -> bool:
    """One fresh server over the weights of ``weight_seed``; every prompt
    served for ``new`` tokens and scored. A table per prompt that misses
    ``tol`` (or all of them when there is one seed), a line otherwise."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference, weights
    from bigdl_tpu.llm.models.llama import LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    params = jax.block_until_ready(
        weights.conditioned_q4_params(cfg, weight_seed))
    model = LlamaForCausalLM(cfg, params, max_cache_len=128)
    srv = LLMServer(model, max_batch=16, max_seq_len=seq).start()
    ok = True
    try:
        for prompt in prompts:
            n = len(prompt)
            served = srv.submit(prompt, max_new_tokens=new).get(timeout=1200)
            ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            rows = slice(n - 1, n - 1 + new)
            ref = reference.llama_logits(cfg, params, ids)[rows]
            dense = np.asarray(
                model(jnp.asarray(ids)[None])[0][0], np.float32)[rows]
            m_ref = reference.margins(ref, served)
            m_dense = reference.margins(dense, served)
            # noise floor: where (b)'s own argmax sits under (a), and the
            # plain logit distance in units of (a)'s spread
            floor = reference.margins(ref, dense.argmax(-1))
            dist = np.abs(ref - dense).max(-1) / ref.std(-1)
            good = float(m_ref.max()) <= tol
            ok &= good
            print(f"# weights {weight_seed} prompt {n}: worst margin vs f32 "
                  f"{m_ref.max():.4f} at position {n + int(m_ref.argmax())}"
                  f"; vs dense {m_dense.max():.4f}; noise floor (dense "
                  f"argmax under f32) {floor.max():.4f}; max logit distance "
                  f"{dist.max():.4f} sigma; pass_errors {srv.pass_errors}; "
                  f"device bytes in use "
                  f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}"
                  f" -> {'ok' if good else 'MISSED'}", flush=True)
            if good and len(prompts) == 1:
                continue
            print("# pos served f32_argmax f32_argmin dense_argmax  "
                  "margin_vs_f32  margin_vs_dense  max|f32-dense|/sigma")
            for i in range(new):
                print(f"  {n + i:4d} {served[i]:6d} {int(ref[i].argmax()):6d}"
                      f" {int(ref[i].argmin()):6d} {int(dense[i].argmax()):6d}"
                      f"  {m_ref[i]:12.4f}  {m_dense[i]:14.4f}  "
                      f"{dist[i]:18.4f}{'  *' if (n + i) % 16 == 0 else ''}")
    finally:
        srv.stop()
    return ok


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="*", default=None)
    ap.add_argument("--new", type=int, default=16,
                    help="tokens served per prompt (prompt + new <= 128)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import bigdl_tpu  # noqa: F401  (places the compile cache)
    import jax

    from benchmark import manifest as mf
    from bigdl_tpu.llm.models.llama import LlamaConfig

    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU; use --rehearse for the CPU rehearsal", file=sys.stderr)
        return 3
    cfg = LlamaConfig.tiny() if args.rehearse else LlamaConfig.mistral_7b()
    seq = 128 if args.rehearse else 2048
    with open(os.path.join(mf.HERE, "configs", "mistral7b_int4.json")) as f:
        tol = float(json.load(f)["reference_tolerance_sigma"])
    if args.seed is None:
        rs = np.random.RandomState(7)
        jobs = [(0, [rs.randint(0, cfg.vocab_size, n).astype(np.int32)
                     for n in (64, 63, 65)])]
    else:   # as drivers/serve.py derives a run's weights and check prompt
        jobs = [(s % (2 ** 31 - 1),
                 [np.random.RandomState(s % (2 ** 31)).randint(
                     0, cfg.vocab_size, 64).astype(np.int32)])
                for s in args.seed]
    missed = 0
    for weight_seed, prompts in jobs:
        missed += not check(cfg, seq, weight_seed, prompts, tol, args.new)
        gc.collect()    # the last server's pool, before the next is built
    print(f"# {len(jobs) - missed} of {len(jobs)} weight sets served every "
          f"token within {tol} sigma of the float32 reference")
    return 1 if missed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
