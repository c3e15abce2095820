"""Where a sparse (DeepSeek Sparse Attention) decode step of a
``glm_moe_dsa`` configuration spends its device time: the engine's
decode step at the configuration's widths on made-up weights and pools
(``--rows`` live rows of ``--tokens`` cached tokens each, the rest of
the batch dead), timed over ``--steps`` steps and traced once
(``benchmark/trace_reduce``: device time by op, exclusive), then each
decode part alone: the scoring kernel, the selection (``lax.top_k`` and
the bisection form prefill uses), the gather of the selected rows and
the sparse kernel. The selection is timed in its three forms: the sort
(``dsa_select_reference``), the bisection prefill uses, and the kernel
``dsa_topk_decode`` (its threshold alone, and whole: the compaction is
their difference), with the kernel's kept sets checked against the
sort's (``topk_exact``); the whole step is timed with the sort too.

    python3 tools/exp_dsa_decode.py [--rows 18] [--tokens 22000] [--groups 2,8]
    JAX_PLATFORMS=cpu python3 tools/exp_dsa_decode.py --tiny

Writes its table to stdout and
``chiprun_out/exp_dsa_decode_<rows>x<tokens>.json``."""

import argparse
import functools
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(fn, *args, n=10):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=18)
    ap.add_argument("--tokens", type=int, default=22000)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--groups", default="",
                    help="TOPK_GROUP values to time the kernel at too")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest as mf
    from benchmark import trace_reduce
    from benchmark.drivers import serve_glm5 as drv
    from bigdl_tpu.llm.kernels import sparse_attention as sa
    from bigdl_tpu.llm.models import deepseek as ds

    man = mf.load()
    config = mf.config_of(man, mf.cell(man, "glm5_longctx_steady"))
    reh = config["rehearse"] if a.tiny else {}
    cfg = drv.model_config(config, reh.get("model", {}))
    eng = {**config["engine"], **reh.get("engine", {})}
    b, page = eng["max_batch"], eng["page_size"]
    a.rows = min(a.rows, b)
    tokens = min(a.tokens, eng["max_seq_len"] - 8) if not a.tiny else 300
    pmax = -(-eng["max_seq_len"] // page)
    pmax = -(-pmax // 8) * 8
    live_pages = -(-tokens // page) + 1
    num_pages = 1 + a.rows * live_pages
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: ds.init_params(cfg, 0, jnp.bfloat16))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(key, len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        (jax.random.normal(k, s.shape, jnp.float32) * 0.02).astype(s.dtype)
        if s.size < 5e7 else jnp.zeros(s.shape, s.dtype) + 0.01
        for k, s in zip(keys, leaves)])
    lay = cfg.num_hidden_layers
    kv = jnp.zeros((lay, num_pages, 1, page, cfg.latent_width),
                   jnp.bfloat16)
    ik = jax.random.normal(key, (lay, num_pages, 1, page,
                                 cfg.index_head_dim)).astype(jnp.bfloat16)
    rs = np.random.RandomState(0)
    perm = 1 + rs.permutation(num_pages - 1)
    bt = np.zeros((b, pmax), np.int32)
    lens = np.zeros(b, np.int32)
    for r in range(a.rows):
        bt[r, :live_pages] = perm[r * live_pages:(r + 1) * live_pages]
        lens[r] = tokens
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)
    toks = jnp.asarray(rs.randint(0, cfg.vocab_size, b), jnp.int32)

    def step_time(select):
        nonlocal kv, ik
        with mock.patch.object(sa, "dsa_select", select):
            step = jax.jit(lambda p, k, v, bt, lens, toks:
                           ds.paged_decode_step(p, cfg, k, v, bt, lens,
                                                toks, page=page),
                           donate_argnums=(1, 2))
            out = step(params, kv, ik, bt, lens, toks)
        kv, ik = out[1], out[2]
        jax.block_until_ready(kv)
        t0 = time.perf_counter()
        for _ in range(a.steps):
            out = step(params, kv, ik, bt, lens, toks)
            kv, ik = out[1], out[2]
        jax.block_until_ready(kv)
        return step, (time.perf_counter() - t0) / a.steps * 1e3

    _, sort_ms = step_time(sa.dsa_select_reference)
    step, step_ms = step_time(sa.dsa_select)
    res = {"rows": a.rows, "tokens": tokens, "step_ms_wall": step_ms,
           "step_ms_wall_sort": sort_ms}
    if not a.tiny:
        with trace_reduce.record() as tdir:
            for _ in range(5):
                out = step(params, kv, ik, bt, lens, toks)
                kv, ik = out[1], out[2]
            jax.block_until_ready(kv)
        red = trace_reduce.collect(tdir, 1)
        d0 = red["devices"][0]
        mods = d0["modules"]
        res["modules"] = {k: [v[0], v[1] / max(1, v[0]) * 1e3]
                          for k, v in mods.items()}
        ops = sorted(d0["ops"].items(), key=lambda kv_: -kv_[1])
        res["ops_ms_a_step"] = [(k, v / 5 * 1e3) for k, v in ops[:30]]

    # the parts alone, at one layer's shapes
    iflat = ik.reshape((-1,) + ik.shape[2:])
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    q = jax.random.normal(key, (b, hi, di)).astype(jnp.bfloat16)
    w = jax.random.normal(key, (b, hi))
    scores = sa.index_scores(q, w, iflat, bt, lens, page_size=page)
    cur = jnp.zeros(b)
    k = cfg.index_topk
    at = sa.pool_rows(bt, lens, page=page)
    interpret = True if a.tiny else False
    topk = sa.dsa_topk_decode.__wrapped__

    def threshold(s, c, ln, a_):
        with mock.patch.object(sa, "_topk_kernel", functools.partial(
                sa._topk_kernel, place=False)):
            return topk(s, c, ln, a_, k=k, interpret=interpret)
    parts = {
        "index_scores": _timed(jax.jit(
            lambda q, w, kp, bt, ln: sa.index_scores(
                q, w, kp, bt, ln, page_size=page)), q, w, iflat, bt, lens),
        "dsa_select_sort": _timed(jax.jit(
            lambda s, c, ln: sa.dsa_select_reference(s, c, ln, k=k)),
            scores, cur, lens),
        "topk_kernel_threshold": _timed(jax.jit(threshold), scores, cur,
                                        lens, at),
        "topk_kernel": _timed(jax.jit(
            lambda s, c, ln, a_: sa.dsa_topk_decode(
                s, c, ln, a_, k=k, interpret=interpret)), scores, cur, lens,
            at),
        "topk_kernel_positions": _timed(jax.jit(
            lambda s, c, ln: sa.dsa_topk_decode(
                s, c, ln, k=k, interpret=interpret)), scores, cur, lens),
        "select_mask_bisection": _timed(jax.jit(lambda s: sa.select_mask(
            s, k)), scores),
    }
    def bisect_select(s, c, ln):
        # the bisection's mask, then its positions in order
        pos_ = jnp.arange(s.shape[1])[None]
        s = jnp.where(pos_ < ln[:, None], s, -jnp.inf)
        s = jnp.where(pos_ == ln[:, None], c[:, None], s)
        m = sa.select_mask(s, k)
        cs = jnp.cumsum(m.astype(jnp.int32), axis=1)
        idx = jax.vmap(lambda r: jnp.searchsorted(
            r, jnp.arange(1, k + 1), side="left"))(cs)
        return idx, jnp.arange(1, k + 1)[None] <= cs[:, -1:]
    parts["dsa_select_bisection_positions"] = _timed(
        jax.jit(bisect_select), scores, cur, lens)
    parts["dsa_select_sort_pool_rows"] = _timed(jax.jit(
        lambda s, c, ln, a_: sa.dsa_select_reference(s, c, ln, a_, k=k)),
        scores, cur, lens, at)
    parts["topk_kernel_compaction"] = (parts["topk_kernel"]
                                       - parts["topk_kernel_threshold"])
    for g in (int(x) for x in a.groups.split(",") if x):
        with mock.patch.object(sa, "TOPK_GROUP", g):
            parts[f"topk_kernel_group{g}"] = _timed(jax.jit(
                lambda s, c, ln, a_: topk(s, c, ln, a_, k=k,
                                          interpret=interpret)),
                scores, cur, lens, at)
            parts[f"topk_kernel_threshold_group{g}"] = _timed(
                jax.jit(threshold), scores, cur, lens, at)
    pos, ok = sa.dsa_select_reference(scores, cur, lens, at, k=k)
    got, got_ok = sa.dsa_topk_decode(scores, cur, lens, at, k=k,
                                     interpret=interpret)
    res["topk_exact"] = all(
        sorted(np.asarray(x)[np.asarray(xo)].tolist())
        == sorted(np.asarray(y)[np.asarray(yo)].tolist())
        for x, xo, y, yo in zip(got, got_ok, pos, ok))
    kv_rows = kv.reshape(-1, kv.shape[-1])
    parts["gather_selected"] = _timed(jax.jit(sa.gather_selected), kv_rows,
                                      pos)
    rows = sa.gather_selected(kv_rows, pos)
    qq = jax.random.normal(key, (b, cfg.num_attention_heads,
                                 cfg.latent_width))
    parts["sparse_latent"] = _timed(jax.jit(
        lambda q, r, c: sa.sparse_latent_stats(
            q, r, c, dv=cfg.kv_lora_rank, scale=cfg.attn_scale)), qq, rows,
        ok)
    res["parts_ms_one_layer"] = parts
    print(json.dumps(res, indent=1), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/exp_dsa_decode_{a.rows}x{tokens}.json",
              "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
