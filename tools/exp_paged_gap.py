"""Chip experiment: the paged-vs-dense DMA gaps, decode AND prefill.

Decode (the original experiment): where do the ~9.5 ms between the
paged decode step (54.2 ms, b8 ctx256) and the fused-scan dense-cache
step (~44.7 ms) go? Times three variants of the b8/7B decode step under
the same fori-loop slope harness as bench_paged_decode_step:
  full     — the real serving step (paged_attention_stats + merge + scatter)
  noattn   — attention replaced by v (same matmuls/norms, no paged kernel)
  nomerge  — kernel runs, merge replaced by acc (no combine math)
full-noattn isolates the paged kernel + merge; full-nomerge isolates the
combine. If the kernel dominates, its (b, hkv, nblk)-grid 4 KB page DMAs
are the suspect (per-(page, head) copies are DMA-latency-bound).

Prefill (ISSUE 8 refresh): the dense-staging gather/scatter gap this
PR deleted, timed from the REAL entry points so the before/after stays
reproducible from one tool:
  dense    — llama.paged_prefill_partial: gather n_pp prefix pages into
             a dense temp cache, family forward, scatter the window back
  ragged   — llama.paged_prefill_ragged: attention reads the prefix
             pages in place, only the suffix scatter remains
  dma      — the gather + scatter of the dense sandwich with the layer
             math removed: the staging traffic in isolation
dense − ragged is the end-to-end win; dma bounds how much of it is pure
HBM round-trip (it grows with the prefix while ragged's suffix scatter
does not). Select with --decode / --prefill (default: both); --tiny
swaps in the tiny config for an off-chip smoke."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.llm.kernels.paged_attention import (
    LANE, merge_attention_partial, paged_attention_stats)
from bigdl_tpu.llm.models.llama import (LlamaConfig, _linear,
                                        attention_qkv, mlp, rms_norm,
                                        rope_cfg, synthetic_q4_params)


def build_step(cfg, bt, page, num_pages, mode: str):
    def step(params, k_pages, v_pages, lens, toks):
        b = toks.shape[0]
        L = cfg.num_hidden_layers
        kp_flat = k_pages.reshape((L * num_pages,) + k_pages.shape[2:])
        vp_flat = v_pages.reshape((L * num_pages,) + v_pages.shape[2:])
        x = params["embed_tokens"][toks][:, None]
        positions = lens[:, None].astype(jnp.int32)

        def layer_step(carry, inputs):
            x, = carry
            lp, l = inputs
            h = rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
            q, k, v = attention_qkv(lp, h, cfg)
            q = rope_cfg(q, positions, cfg)
            k = rope_cfg(k, positions, cfg)
            if mode == "noattn":
                attn = jnp.repeat(
                    v[:, 0], cfg.num_attention_heads
                    // cfg.num_key_value_heads, 1).astype(x.dtype)
            else:
                acc, m, lsum = paged_attention_stats(
                    q[:, 0], kp_flat, vp_flat, bt + l * num_pages, lens,
                    page_size=page)
                if mode == "nomerge":
                    attn = (acc / 256.0).astype(x.dtype)
                else:
                    attn = merge_attention_partial(
                        acc, m, lsum, q[:, 0], k[:, 0],
                        v[:, 0]).astype(x.dtype)
            x = x + _linear(lp["o_proj"], attn.reshape(b, 1, -1))
            h2 = rms_norm(x, lp["post_attention_layernorm"],
                          cfg.rms_norm_eps)
            x = x + mlp(lp, h2, x.dtype)
            return (x,), (k[:, 0], v[:, 0])

        (x,), (k_new, v_new) = jax.lax.scan(
            layer_step, (x,), (params["layers"],
                               jnp.arange(cfg.num_hidden_layers)))
        x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
        logits = _linear(params["lm_head"], x)
        pidx = lens // page
        slot = lens % page
        phys = bt[jnp.arange(b), pidx]
        k_pages = k_pages.at[:, phys, :, slot].set(
            k_new.transpose(1, 0, 2, 3).astype(k_pages.dtype))
        v_pages = v_pages.at[:, phys, :, slot].set(
            v_new.transpose(1, 0, 2, 3).astype(v_pages.dtype))
        return (logits[:, 0].astype(jnp.float32), k_pages, v_pages)

    return step


def decode_gap(batch=8, ctx_len=256, page_size=16, cfg=None):
    cfg = cfg or LlamaConfig.llama2_7b()
    params = synthetic_q4_params(cfg)
    ppb = LANE // page_size
    cap = -(-(ctx_len + 160) // page_size)
    pages_cap = -(-cap // ppb) * ppb
    num_pages = 1 + batch * pages_cap
    nl, hkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                   cfg.head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    shape = (nl, num_pages, hkv, page_size, hd)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16) * 0.1
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16) * 0.1
    bt = np.zeros((batch, pages_cap), np.int32)
    for b in range(batch):
        bt[b] = 1 + b * pages_cap + np.arange(pages_cap)
    bt = jnp.asarray(bt)
    lens0 = jnp.full((batch,), ctx_len, jnp.int32)
    toks0 = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (batch,)),
        jnp.int32)

    results = {}
    for mode in ("full", "nomerge", "noattn"):
        step = build_step(cfg, bt, page_size, num_pages, mode)

        @functools.partial(jax.jit, static_argnames=("steps",),
                           donate_argnums=(1, 2))
        def run(params, kp, vp, lens, toks, steps: int):
            def body(i, carry):
                kp, vp, lens, toks = carry
                logits, kp, vp = step(params, kp, vp, lens, toks)
                return (kp, vp, lens + 1,
                        jnp.argmax(logits, -1).astype(jnp.int32))
            return jax.lax.fori_loop(0, steps, body,
                                     (kp, vp, lens, toks))

        kp = k_pages + 0
        vp = v_pages + 0

        def window(n, kp, vp):
            t0 = time.perf_counter()
            kp, vp, lens, toks = run(params, kp, vp, lens0, toks0, n)
            int(np.asarray(toks)[0])
            return time.perf_counter() - t0, kp, vp

        for n in (8, 32):
            _, kp, vp = window(n, kp, vp)
        t_small, kp, vp = window(8, kp, vp)
        t_big, kp, vp = window(32, kp, vp)
        per = (t_big - t_small) / 24
        if per <= 0:
            per = t_big / 32
        results[mode] = round(per * 1e3, 2)
        print(mode, results[mode], "ms/step", flush=True)
    out = {"step_ms": results,
           "attn_plus_merge_ms": round(
               results["full"] - results["noattn"], 2),
           "merge_ms": round(results["full"] - results["nomerge"], 2)}
    print(out)
    return out


def _build_dense_dma(cfg, page, n_pp, bucket):
    """The dense sandwich's memory traffic with the layer math removed:
    gather the n_pp prefix pages into a dense temp buffer, then scatter
    the page-aligned window back. What's left of paged_prefill_partial
    when the forward is deleted — the staging gap in isolation."""
    def dma(k_pages, v_pages, offset, prefix_ids, phys, slots):
        L = k_pages.shape[0]
        s_temp = n_pp * page + page + bucket
        window0 = (offset // page) * page

        def stage(pages):
            g = pages[:, prefix_ids].transpose(0, 1, 3, 2, 4)
            tmp = g.reshape(L, n_pp * page, *g.shape[3:])
            tmp = jnp.pad(tmp, ((0, 0), (0, s_temp - n_pp * page),
                                (0, 0), (0, 0)))
            w = jax.lax.dynamic_slice_in_dim(tmp, window0,
                                             page + bucket, axis=1)
            return pages.at[:, phys, :, slots].set(
                w.transpose(1, 0, 2, 3).astype(pages.dtype))

        return stage(k_pages), stage(v_pages)

    return dma


def prefill_gap(splits=None, page_size=16, cfg=None, repeats=8):
    """Partial-prefill dispatch time at several prefix/suffix splits,
    from the real ISSUE 5 / ISSUE 8 entry points (docstring above)."""
    from bigdl_tpu.llm.models import llama as _llama

    cfg = cfg or LlamaConfig.llama2_7b()
    if splits is None:
        limit = min(256, cfg.max_position_embeddings)
        splits = ((limit * 3 // 4, limit // 4),
                  (limit * 7 // 8, limit // 8))
    params = synthetic_q4_params(cfg)
    nl, hkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                   cfg.head_dim)
    ppb = LANE // page_size
    top = max(s + t for s, t in splits)
    cap = -(-top // page_size)
    pages_cap = -(-cap // ppb) * ppb
    num_pages = 1 + 2 * pages_cap
    kk, kv = jax.random.split(jax.random.PRNGKey(2))
    shape = (nl, num_pages, hkv, page_size, hd)
    k_pages0 = jax.random.normal(kk, shape, jnp.bfloat16) * 0.1
    v_pages0 = jax.random.normal(kv, shape, jnp.bfloat16) * 0.1
    rs = np.random.RandomState(0)
    out = {}
    for prefix, suffix in splits:
        n_pp = 1 << max(0, (-(-prefix // page_size)) - 1).bit_length()
        bucket = max(page_size, 1 << (suffix - 1).bit_length())
        prefix_pages = list(range(1, 1 + -(-prefix // page_size)))
        own = list(range(1 + len(prefix_pages), 1 + pages_cap))
        row = np.zeros(pages_cap, np.int32)
        row[:len(prefix_pages) + len(own)] = prefix_pages + own
        T = prefix + suffix
        pos = prefix + np.arange(bucket)
        phys_b = np.where(pos < T, row[np.minimum(pos // page_size,
                                                  pages_cap - 1)],
                          0).astype(np.int32)
        slots_b = (pos % page_size).astype(np.int32)
        # the dense path's page-aligned window (page + bucket wide)
        w0 = (prefix // page_size) * page_size
        wpos = w0 + np.arange(page_size + bucket)
        phys_w = np.where((wpos >= prefix) & (wpos < T),
                          row[np.minimum(wpos // page_size,
                                         pages_cap - 1)],
                          0).astype(np.int32)
        slots_w = (wpos % page_size).astype(np.int32)
        pids = np.zeros(n_pp, np.int32)
        pids[:len(prefix_pages)] = prefix_pages
        toks = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, bucket)),
                           jnp.int32)
        args = dict(
            length=jnp.asarray(suffix, jnp.int32),
            offset=jnp.asarray(prefix, jnp.int32),
            pids=jnp.asarray(pids), bt=jnp.asarray(row),
            phys_b=jnp.asarray(phys_b), slots_b=jnp.asarray(slots_b),
            phys_w=jnp.asarray(phys_w), slots_w=jnp.asarray(slots_w))

        # cfg is a plain dataclass (unhashable): close over it like the
        # engine's builders do instead of marking it static
        npp_, bkt_ = n_pp, bucket
        dense = jax.jit(
            lambda params, kp, vp, *a: _llama.paged_prefill_partial(
                params, cfg, kp, vp, *a, page=page_size, n_pp=npp_,
                bucket=bkt_, cache_dtype=jnp.bfloat16),
            donate_argnums=(1, 2))
        ragged = jax.jit(
            lambda params, kp, vp, *a: _llama.paged_prefill_ragged(
                params, cfg, kp, vp, *a, page=page_size),
            donate_argnums=(1, 2))
        dma = jax.jit(_build_dense_dma(cfg, page_size, n_pp, bucket),
                      donate_argnums=(0, 1))
        zero = jnp.asarray(0, jnp.int32)

        def run_dense(kp, vp):
            out = dense(params, kp, vp, toks, args["length"],
                        args["offset"], args["pids"], args["phys_w"],
                        args["slots_w"])
            return out[0], out[1]

        def run_ragged(kp, vp):
            out = ragged(params, kp, vp, toks, args["length"],
                         args["offset"], args["bt"], args["phys_b"],
                         args["slots_b"], zero, zero)
            return out[0], out[1]

        def run_dma(kp, vp):
            return dma(kp, vp, args["offset"], args["pids"],
                       args["phys_w"], args["slots_w"])

        entry = {"prefix": prefix, "suffix": suffix, "n_pp": n_pp,
                 "bucket": bucket}
        for name, fn in (("dense", run_dense), ("ragged", run_ragged),
                         ("dma", run_dma)):
            kp, vp = k_pages0 + 0, v_pages0 + 0
            kp, vp = fn(kp, vp)                       # compile + warm
            jax.block_until_ready(kp)
            t0 = time.perf_counter()
            for _ in range(repeats):
                kp, vp = fn(kp, vp)
            jax.block_until_ready(kp)
            entry[f"{name}_ms"] = round(
                (time.perf_counter() - t0) / repeats * 1e3, 3)
        entry["staging_gap_ms"] = round(
            entry["dense_ms"] - entry["ragged_ms"], 3)
        out[f"{prefix}+{suffix}"] = entry
        print(entry, flush=True)
    return out


def main(argv=()):
    tiny = "--tiny" in argv
    cfg = LlamaConfig.tiny() if tiny else None
    which = [a for a in ("--decode", "--prefill") if a in argv] or \
        ["--decode", "--prefill"]
    out = {}
    if "--decode" in which:
        out["decode"] = decode_gap(cfg=cfg) if not tiny else decode_gap(
            batch=2, ctx_len=32, page_size=8, cfg=cfg)
    if "--prefill" in which:
        out["prefill"] = prefill_gap(cfg=cfg, page_size=8 if tiny
                                     else 16)
    return out


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
