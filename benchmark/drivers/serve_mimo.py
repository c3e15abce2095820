"""The serving driver for configurations of the ``mimo_v2`` family
(window and full layers in two page classes, an expert-parallel share):
one ``LLMServer`` on one chip under the cell's open-loop traffic,
measured from the caller's side.

From ``drivers/serve.py`` it takes everything that is no family's
(``Observer``, ``Track``, ``_submit``, ``_sleep_until``, ``_warm``,
``_annotate_engine``, ``_program_names``, ``_delta``) and from
``drivers/serve_deepseek.py`` what is not the latent cache's
(``scheduled_requests``, ``row_distance``, ``cached_distance``,
``_family_counters``, ``_memory``; its ``serve_with_company`` builds
the latent tap inside, so :func:`serve_tapped` repeats its dozen lines
around this family's). The window
loop itself is :func:`run_window`, ``serve_deepseek.run`` with the five
things a family brings (its configuration, weights, model class,
reference check, in its two halves, and one line of the report) taken
as arguments: the two
older drivers build theirs inside their ``run`` and cannot be given
another's, and this PR may not edit them (PERF.md §7 asks a
``benchmark`` PR to point all three at this one).

``correct`` is ``serve.py``'s (no failed or wrong request, no pass
error, no program first used inside the window, the kernel programs
hold their Mosaic calls) and, after the window, at the run's widths, a
chain from what the ENGINE served to the float32 reference. One prompt
is served while other rows are live; :class:`Tap` keeps the logits row
the engine computed at its prefill and at each of its decode steps,
the rows both page classes cached for it, and the answer of the served
decode kernels to one probe query over them. That is the check's
served half (:func:`serve_for_check`); a run then lets the engine and
its pools go and computes the comparisons (:func:`compare_served`)
beside the weights alone, so that ``memory_peak_bytes`` is what the
served state held.

- (d) served against dense: the engine's logits rows against the
  program's dense bfloat16 ``forward`` over the same ids (the same
  weights and arithmetic, but contiguous caches, no page, no ring, no
  kernel) in the median over the positions; its tokens the argmax of
  its own rows; every row it cached, of the full class and of what the
  window class's ring still holds, against the dense forward's cache;
  the probe: one random query a class through the served decode
  dispatch (kernel, table or ring, window) over the request's pages
  and through the served merge (the sink), against a plain softmax
  over the dense forward's rows. A logits row moves by a hundredth
  when a window layer sees one key more or fewer; the probe moves by a
  tenth. The engine's ``full_ctx_tokens_total`` and
  ``window_ctx_tokens_total`` must be the sums of the lengths the tap
  saw dispatched.
- (a) served against reference: every served token within the
  configuration's tolerance of the float32 reference's maximum, the
  engine's rows within a limit of the reference's in the median, and
  the rows the engine cached within a limit of the reference's own
  keys and values, in the median of every layer (layer 0's depend on
  nothing but the embedding: a
  wrong rotary base, rotary width or value scale is a fifth of a row
  or more there, whatever the layers after make of it).
- (b) the engine's counters: assignments computed here and assignments
  left to the other shares add up to ``num_experts_per_tok`` for every
  token and expert layer of the whole run.
- (c) dense against reference: the same experts for the configuration's
  share of (token, layer) pairs, and the program's ``route`` on the
  very inputs the reference's router was given agrees on all but exact
  ties, with the weights to a part in ten thousand.

``benchmark/check_mimo.py`` measures the floors these limits come from
and plants faults in the served program to see each one fail.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Dict, List

import numpy as np

from benchmark import reference, reference_mimo, stats, trace_reduce, \
    weights_mimo
from benchmark.drivers.serve import (Observer, Track, _annotate_engine,
                                     _delta, _program_names, _sleep_until,
                                     _submit, _warm)
from benchmark.drivers.serve_deepseek import (_family_counters, _memory,
                                              cached_distance,
                                              row_distance,
                                              scheduled_requests)


def model_config(config: Dict, override: Dict):
    from bigdl_tpu.llm.models.mimo import MimoConfig
    return MimoConfig.from_hf_config({**config, **override})


def seeded_params(cfg, seed: int, config: Dict):
    return weights_mimo.seeded_bf16_params(
        cfg, seed, float(config["weights_back_gain"]),
        float(config["weights_sink_mean"]),
        int(config["weights_router_seed"]))


class Tap:
    """What the engine computed for ONE request (the one whose prompt is
    ``prompt``), as device arrays, taken on the engine thread where the
    engine holds them. ``rows``: the last-position logits row of its
    prefill, then its row of every decode step (row ``k`` is what served
    token ``k`` was sampled from). ``live``: rows each of those steps
    advanced. Right after its last step: ``cached``, its pages of both
    classes gathered through the engine's own tables, a page at a time
    (a slice of a pool in its own layout, as the kernel's DMA is), and
    ``probe``, the served decode dispatch and merge on one random query
    a class over those pages. ``ctx`` sums, over every decode dispatch
    while the tap is on, the lengths the engine dispatched: the host's
    own count for the family's two context counters."""

    def __init__(self, srv, cfg, prompt: np.ndarray, seed: int):
        import jax
        import jax.numpy as jnp
        self.rows: List = []
        self.live: List[int] = []
        self.cached, self.cached_len, self.probe = None, 0, None
        self.probe_len = 0
        self.ctx = {"full_ctx_tokens_total": 0, "window_ctx_tokens_total": 0,
                    "steps": 0}
        self._srv = srv
        finish, after = srv._finish_prefill, srv._after_dispatch
        rs = np.random.RandomState(seed % (2 ** 31))
        nh, kw = cfg.num_attention_heads, cfg.k_width
        # one probe query a class and the key and value of a "current
        # token" for the merge: unit-spread scores, as the model's are
        self.q = [_padded(rs.randn(1, nh, cfg.head_dim), kw) for _ in (0, 1)]
        self.k_new = [_padded(rs.randn(1, cfg.kv_heads(kind), cfg.head_dim),
                              kw) for kind in (0, 1)]
        self.v_new = [_padded(rs.randn(1, cfg.kv_heads(kind),
                                       cfg.v_head_dim), cfg.v_width)
                      for kind in (0, 1)]

        def mine(req) -> bool:
            p = req.prompt_ids
            return p.shape == prompt.shape and bool((p == prompt).all())

        def finish_prefill(i, req, row_pages, own, last, *a, **k):
            if mine(req):
                self.rows.append(last)
            return finish(i, req, row_pages, own, last, *a, **k)

        def gather(pool, pids):
            return jnp.stack([jax.lax.dynamic_index_in_dim(
                pool, pid, 1, False) for pid in pids], axis=1)

        @functools.partial(jax.jit, static_argnames=("kind",))
        def probed(pool, table, cached, q, k_new, v_new, sink, *, kind):
            # jitted: the (L, P, ...) -> (L·P, ...) view is a bitcast
            # here and a copy of the pool when made eagerly
            from bigdl_tpu.llm.kernels import hybrid_attention as ha
            from bigdl_tpu.llm.kernels import paged_attention as pa
            acc, m, lsum = ha.attention_decode_stats(
                q, pool.reshape((-1,) + pool.shape[2:]), table, cached,
                page_size=srv._page, scale=cfg.attn_scale,
                window=cfg.sliding_window if kind else None)
            return pa.merge_attention_partial(
                acc, m, lsum, q, k_new, v_new, scale=cfg.attn_scale,
                sink=sink)

        def probe(kind, table):
            pool = srv._k_pages[kind]
            sink = None
            if cfg.has_sink(kind):
                sink = srv.model.params["layers"][
                    cfg.layers_of(kind)[0]]["sink"]
            return probed(
                pool, jnp.asarray(table[None]),
                jnp.asarray([self.probe_len], jnp.int32),
                *(jnp.asarray(a[kind], pool.dtype)
                  for a in (self.q, self.k_new, self.v_new)), sink,
                kind=kind)

        def after_dispatch(rec, t0):
            if rec.get("fn") == "llm/decode_paged":
                lens = np.asarray([srv._lens[i] - 1 for i, _ in rec["pairs"]])
                self.ctx["full_ctx_tokens_total"] += int(lens.sum())
                self.ctx["window_ctx_tokens_total"] += int(
                    np.minimum(lens, cfg.sliding_window).sum())
                self.ctx["steps"] += 1
                for i, req in rec["pairs"]:
                    if mine(req):
                        self.rows.append(srv._last[i])
                        self.live.append(len(rec["pairs"]))
                        if srv._remaining[i] == 0:      # its last step
                            self.cached_len = int(srv._lens[i])
                            # the probe asks half a page before the
                            # end: there the window reaches back into
                            # a ninth page (953..1079 at 1,080), the
                            # one a ring a page short has recycled
                            self.probe_len = self.cached_len \
                                - srv._page // 2
                            pages = -(-self.cached_len // srv._page)
                            ring = srv._rings[0]
                            tables = (srv._bt[i, :pages].copy(),
                                      ring.bt[i].copy())
                            self.ring = ring.ring
                            self.cached = [
                                gather(pool, t.tolist()) for pool, t in
                                zip(srv._k_pages, tables)]
                            # (layer 0 of each class: table + 0 * P)
                            self.probe = [probe(0, srv._bt[i].copy()),
                                          probe(1, ring.bt[i].copy())]
            return after(rec, t0)

        srv._finish_prefill, srv._after_dispatch = \
            finish_prefill, after_dispatch

    def close(self):
        """Take the tap off and let go of the engine. Returns the logits
        rows (served tokens + 1, vocab) float32."""
        del self._srv._finish_prefill, self._srv._after_dispatch
        self._srv = None
        return np.stack([np.asarray(r, np.float32) for r in self.rows]) \
            if self.rows else np.zeros((0, 0), np.float32)

    def cached_rows(self, cfg, page: int):
        """By class: ``(positions held (n,), keys (Lc, n, hkv, d), values
        (Lc, n, hkv, dv))`` float32, at the model's own widths."""
        out = []
        for kind, pool in enumerate(self.cached or ()):
            c = np.asarray(pool, np.float32)        # (Lc, pages, hkv, page, W)
            lc, pages, hkv, _, w = c.shape
            c = c.transpose(0, 1, 3, 2, 4).reshape(lc, pages * page, hkv, w)
            if kind == 0:
                pos = np.arange(self.cached_len)
                at = pos
            else:
                cur = (self.cached_len - 1) // page
                lo = max(0, (cur - self.ring + 1) * page)
                pos = np.arange(lo, self.cached_len)
                at = (pos // page) % self.ring * page + pos % page
            out.append((pos, c[:, at, :, :cfg.head_dim],
                        c[:, at, :, cfg.k_width:cfg.k_width
                          + cfg.v_head_dim]))
        return out


def _padded(a: np.ndarray, width: int) -> np.ndarray:
    out = np.zeros(a.shape[:-1] + (width,), np.float32)
    out[..., :a.shape[-1]] = a
    return out


def dense_forward(cfg, params, ids, rows: slice):
    """The program's own dense bfloat16 forward over ``ids`` (T,):
    ``(logits rows ``rows`` (n, vocab) float32, chosen experts [(T, k)
    an expert layer], its contiguous caches [(keys (T, hkv, d), values
    (T, hkv, dv)) a layer] float32)``. Only the rows that are compared
    leave the program: (T, vocab) float32 is 0.66 GB at 1,088."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models import mimo
    t = len(ids)

    def fwd(p, toks):
        logits, cache, chosen = mimo.forward(
            p, cfg, toks, mimo.init_cache(cfg, 1, t), jnp.arange(t)[None],
            routes=True)
        return logits[0, rows], cache, chosen
    logits, cache, chosen = jax.jit(fwd)(
        params, jnp.asarray(ids, jnp.int32)[None])
    return (np.asarray(logits, np.float32),
            [np.asarray(c) for c in chosen],
            [(np.asarray(k[0], np.float32), np.asarray(v[0], np.float32))
             for k, v in cache["kv"]])


def program_router(cfg):
    """The program's router as ``router_on_reference_inputs`` wants it."""
    import jax

    from bigdl_tpu.llm.models import mimo
    return jax.jit(lambda router, h: mimo.route(router, h, cfg))


def probe_distance(tap: Tap, cfg, kind: int, rows, sink) -> float:
    """The probe's answer for one class against a plain float64 softmax
    of the probe query over ``rows`` (keys (T, hkv, d), values (T, hkv,
    dv) of the class's first layer) up to the probe's length, half a
    page short of the request's end (for the window class the last
    ``sliding_window - 1`` of them), the probe's own current token
    and, where the layer has one, its sink: root-mean-square
    difference over the heads' outputs in units of their root mean
    square."""
    if tap.probe is None:
        return float("inf")
    keys, values = (np.asarray(a, np.float64) for a in rows)
    n = tap.probe_len
    lo = max(0, n - cfg.sliding_window + 1) if kind else 0
    g = cfg.num_attention_heads // cfg.kv_heads(kind)
    q = tap.q[kind][0, :, :cfg.head_dim].astype(np.float64)
    k = np.concatenate([keys[lo:n], tap.k_new[kind][:, :, :cfg.head_dim]])
    v = np.concatenate([values[lo:n],
                        tap.v_new[kind][:, :, :cfg.v_head_dim]])
    v = np.repeat(v, g, axis=1)
    s = np.einsum("hd,shd->hs", q, np.repeat(k, g, axis=1)) * cfg.attn_scale
    if sink is not None:
        s = np.concatenate([s, np.asarray(sink, np.float64)[:, None]], 1)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hs,shd->hd", p[:, :v.shape[0]], v)
    got = np.asarray(tap.probe[kind], np.float64)[0, :, :cfg.v_head_dim]
    return float(np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean()))


def serve_tapped(srv, cfg, prompt: np.ndarray, new: int, company: int,
                 rs, seed: int):
    """``serve_deepseek.serve_with_company`` with this family's tap:
    ``prompt`` served for ``new`` tokens while ``company`` other
    requests decode beside it (prompts of its own prefill bucket, so no
    program is new; each outlives it). The tap goes on while the engine
    is idle, so that what it sees dispatched and what the engine counts
    at its drains are the same steps. Returns ``(served tokens, the
    engine's logits rows, the tap, the engine's step counters when the
    tap went on)``."""
    n, vocab = len(prompt), cfg.vocab_size
    low = max(2, (1 << (n - 1).bit_length()) // 2 + 1)
    deadline = time.perf_counter() + 300
    while not srv.engine_idle() and time.perf_counter() < deadline:
        time.sleep(0.01)
    tap = Tap(srv, cfg, prompt, seed)
    before = dict(srv.step_counters)
    others = [srv.submit(
        rs.randint(0, vocab, rs.randint(low, n + 1)).astype(np.int32),
        max_new_tokens=2 * new + company) for _ in range(company)]
    deadline = time.perf_counter() + 600
    while others and time.perf_counter() < deadline and \
            not all(o.tokens or o.done.is_set() for o in others):
        time.sleep(0.005)
    try:
        served = srv.submit(prompt, max_new_tokens=new).get(timeout=600)
        for o in others:
            o.get(timeout=600)
        while not srv.engine_idle() and time.perf_counter() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)             # the last steps in flight drained
    finally:
        rows = tap.close()
    return served, rows[:new], tap, before


LIMITS = ("served_distance_median_max", "cached_row_distance_max",
          "probe_distance_max", "reference_tolerance_sigma",
          "reference_distance_median_max",
          "reference_cached_distance_median_max", "expert_agreement_min",
          "router_agreement_min", "router_weight_tolerance")


def judge(r: Dict, config: Dict) -> Dict[str, bool]:
    """The four verdicts from the readings ``reference_check`` took and
    the configuration's limits, and from nothing else (so that
    ``check_mimo.py --rejudge`` can hold kept readings to limits chosen
    after them)."""
    lim = {k: float(config[k]) for k in LIMITS}
    return {
        "d": bool(r["rows_taken"] and r["tokens_are_argmax_of_rows"]
                  and r["rows_live_min"] >= 2
                  and r["ctx_counters_agree"]
                  and r["served_distance_median"]
                  <= lim["served_distance_median_max"]
                  and r["cached_row_distance_max"]
                  <= lim["cached_row_distance_max"]
                  and r["probe_distance_max"] <= lim["probe_distance_max"]),
        "a": bool(r["reference_finite"]
                  and r["margin_sigma_max"]
                  <= lim["reference_tolerance_sigma"]
                  and r["reference_distance_median"]
                  <= lim["reference_distance_median_max"]
                  and r["reference_cached_distance_median"]
                  <= lim["reference_cached_distance_median_max"]),
        "b": bool(r["token_layers"] > 0
                  and r["assignments"] + r["assignments_elsewhere"]
                  == r["experts_per_token"] * r["token_layers"]),
        "c": bool(r["same_experts"] >= lim["expert_agreement_min"]
                  and r["router_alone_share"] >= lim["router_agreement_min"]
                  and r["router_alone_weight_off"]
                  <= lim["router_weight_tolerance"])}


def reference_check(srv, cfg, params, seed: int, config: Dict, sizes: Dict,
                    say) -> Dict:
    """Family: the chain of the module's docstring, its two halves one
    after the other (a run of the cell lets the engine go between
    them). Returns the four verdicts under ``"d"``, ``"a"``, ``"b"``,
    ``"c"`` (all must hold) and what was compared under
    ``"readings"``."""
    return compare_served(
        cfg, params, serve_for_check(srv, cfg, seed, sizes), config, say)


def serve_for_check(srv, cfg, seed: int, sizes: Dict) -> Dict:
    """The served half of the check: the prompt served beside its
    company with the tap on, and everything the comparison wants of the
    engine taken to the host, so that the engine and its 4.5 GB of
    pools can go before the dense forward and the float32 reference are
    computed beside the weights (``memory_peak_bytes`` is then what the
    served state held, not what the check did)."""
    t0 = time.perf_counter()
    n, new = sizes["prompt_tokens"], sizes["served_tokens"]
    rs = np.random.RandomState(seed % (2 ** 31))
    prompt = rs.randint(0, cfg.vocab_size, n).astype(np.int32)
    served, rows, tap, before = serve_tapped(
        srv, cfg, prompt, new, int(sizes["company"]), rs, seed)
    counted = {k: srv.step_counters[k] - before[k] for k in tap.ctx
               if k != "steps"}
    return {"t0": t0, "prompt": prompt, "new": new, "served": served,
            "rows": rows, "tap": tap, "counted": counted,
            "cached": tap.cached_rows(cfg, srv._page),
            "counters": dict(srv.step_counters)}


def compare_served(cfg, params, took: Dict, config: Dict, say) -> Dict:
    """The comparing half: what :func:`serve_for_check` took against
    the program's dense forward and the float32 reference."""
    t0, prompt, new, served, rows, tap, counted = (
        took[k] for k in ("t0", "prompt", "new", "served", "rows", "tap",
                          "counted"))
    n = len(prompt)
    # the last served token was fed (and cached) but nothing drawn
    # after it: the dense forward takes it too, the comparisons of
    # logits stop before it
    ids = np.concatenate([prompt, np.asarray(served, np.int32)])
    dense, dense_chosen, dense_cache = dense_forward(
        cfg, params, ids, slice(n - 1, n - 1 + new))
    routing, ref_cache = [], []
    logits, chosen = reference_mimo.mimo_logits(
        cfg, params, ids[:-1], routing=routing, rows=ref_cache)
    ref = logits[n - 1:n - 1 + new]
    taken = len(served) == new and rows.shape == dense.shape \
        and tap.cached is not None and tap.cached_len == len(ids)
    nothing = np.full(new, np.inf)
    dist = row_distance(rows, dense) if taken else nothing
    ref_dist = row_distance(rows, ref) if taken else nothing
    c_dist, rc_dist, probes, rc_layers = [np.inf], [np.inf], [np.inf], \
        [np.inf]
    if taken:
        c_dist, rc_dist, probes = [], [], []
        for kind, (pos, keys, values) in enumerate(took["cached"]):
            layers = cfg.layers_of(kind)
            for j, l in enumerate(layers):
                got = np.concatenate([keys[j], values[j]], -1)
                got = got.reshape(len(pos), -1)
                for cache, into, upto in ((dense_cache, c_dist, len(ids)),
                                          (ref_cache, rc_dist,
                                           len(ids) - 1)):
                    k, v = cache[l]
                    at = pos[pos < upto]
                    want = np.concatenate([k[at], v[at]], -1)
                    into.append(cached_distance(
                        got[:len(at)], want.reshape(len(at), -1)))
            sink = params["layers"][layers[0]].get("sink")
            probes.append(probe_distance(tap, cfg, kind,
                                         dense_cache[layers[0]], sink))
        # by layer: a fault of one kind of layer must not hide in the
        # median over the rows of both
        rc_layers = [float(np.median(d)) for d in rc_dist]
        c_dist, rc_dist = np.concatenate(c_dist), np.concatenate(rc_dist)
    m = reference.margins(ref, served) if len(served) == new else nothing
    same = reference_mimo.same_experts(
        chosen, [c[:-1] for c in dense_chosen])
    r_share, w_off = reference_mimo.router_on_reference_inputs(
        program_router(cfg), params, routing)
    c = took["counters"]
    r = {"rows_taken": bool(taken),
         "tokens_are_argmax_of_rows": bool(
             taken and (rows.argmax(-1) == np.asarray(served)).all()),
         "rows_live_min": min(tap.live, default=0),
         "rows_live_max": max(tap.live, default=0),
         "ctx_counters_agree": bool(tap.ctx["steps"] > 0 and all(
             counted[k] == tap.ctx[k] for k in counted)),
         "ctx_counted": counted, "ctx_dispatched": dict(tap.ctx),
         "served_distance_median": float(np.median(dist)),
         "served_distance_max": float(np.max(dist)),
         "cached_row_distance_max": float(np.max(c_dist)),
         "cached_row_distance_median": float(np.median(c_dist)),
         "probe_distance_max": float(np.max(probes)),
         "probe_distances": [float(p) for p in probes],
         "reference_finite": bool(np.all(np.isfinite(logits))),
         "margin_sigma_max": float(np.max(m)),
         "margin_sigma_mean": float(np.mean(m)),
         "reference_distance_median": float(np.median(ref_dist)),
         "reference_distance_max": float(np.max(ref_dist)),
         "reference_cached_distance_median": float(np.max(rc_layers)),
         "reference_cached_distance_by_layer": [
             round(x, 4) for x in rc_layers],
         "reference_cached_distance_max": float(np.max(rc_dist)),
         "assignments": int(c["moe_assignments_total"]),
         "assignments_elsewhere": int(c["moe_assignments_elsewhere_total"]),
         "token_layers": int(c["moe_token_layers_total"]),
         "experts_per_token": int(cfg.num_experts_per_tok),
         "same_experts": float(same.mean()),
         "same_experts_by_layer": [round(float(x), 3)
                                   for x in same.mean(1)],
         "router_alone_share": r_share, "router_alone_weight_off": w_off}
    ok = judge(r, config)

    def word(k):
        return "ok" if ok[k] else "FAILED"
    say(f"reference: (d) served against dense: the engine's {len(rows)} "
        f"logits rows for the served request (its tokens "
        f"{'are' if r['tokens_are_argmax_of_rows'] else 'ARE NOT'} their "
        f"argmax; {r['rows_live_min']}-{r['rows_live_max']} rows live at "
        f"its steps) lie {r['served_distance_median']:.4f} (median; max "
        f"{r['served_distance_max']:.4f}) of a row's spread from the "
        f"program's dense bfloat16 forward over the same ids, at most "
        f"{config['served_distance_median_max']} in the median; the "
        f"{np.size(c_dist)} rows both classes cached for it lie at most "
        f"{r['cached_row_distance_max']:.4f} (median "
        f"{r['cached_row_distance_median']:.4f}) of a row's size from "
        f"that forward's caches, at most "
        f"{config['cached_row_distance_max']}; the probe through the "
        f"served decode kernels lies {r['probe_distances']} (full, "
        f"window) from a plain softmax over them, at most "
        f"{config['probe_distance_max']}; context counters "
        f"{counted} against {tap.ctx} dispatched -> {word('d')}")
    say(f"reference: (a) served against reference: {new} served tokens "
        f"after a {n}-token prompt lie at most "
        f"{r['margin_sigma_max']:.4f} (mean {r['margin_sigma_mean']:.4f}) "
        f"logit-sigmas below the float32 reference's maximum; "
        f"{int((m == 0).sum())}/{new} are its argmax; tolerance "
        f"{config['reference_tolerance_sigma']}; the engine's rows lie "
        f"{r['reference_distance_median']:.4f} (median; max "
        f"{r['reference_distance_max']:.4f}) of a row's spread from the "
        f"reference's, at most {config['reference_distance_median_max']} "
        f"in the median; its cached rows "
        f"{r['reference_cached_distance_median']:.4f} (the largest of the "
        f"layers' medians {r['reference_cached_distance_by_layer']}, full "
        f"class then window class; max "
        f"{r['reference_cached_distance_max']:.4f}) from the reference's "
        f"keys and values, at most "
        f"{config['reference_cached_distance_median_max']} -> {word('a')}")
    say(f"reference: (b) counters {r['assignments']} assignments computed "
        f"here + {r['assignments_elsewhere']} left to the other shares "
        f"over {r['token_layers']} (token, expert layer) pairs = "
        f"{r['experts_per_token']} each: {word('b')}")
    say(f"reference: (c) dense against reference: the same experts for "
        f"{r['same_experts']:.4f} of {same.size} (token, layer) pairs (by "
        f"layer {r['same_experts_by_layer']}), at least "
        f"{config['expert_agreement_min']}; the program's router on the "
        f"reference's router inputs for {r_share:.5f} (at least "
        f"{config['router_agreement_min']}) with weights within "
        f"{w_off:.2e} (at most {config['router_weight_tolerance']}) -> "
        f"{word('c')}; {time.perf_counter() - t0:.2f} s")
    return {**ok, "readings": r}


def report_family(say, win: Dict, cfg, srv) -> None:
    """The family's line of the window's report."""
    layer_steps = max(1, win["moe_layer_steps_total"])
    steps = layer_steps / cfg.num_moe_layers
    say(f"window: held experts with a token "
        f"{win['moe_experts_touched_total'] / layer_steps:.2f} of "
        f"{cfg.experts_held} (of {cfg.n_routed_experts} routed over) and "
        f"fullest {win['moe_max_load_total'] / layer_steps:.2f} tokens per "
        f"expert layer and step; assignments computed here "
        f"{win['moe_assignments_total']}, left to other shares "
        f"{win['moe_assignments_elsewhere_total']}; cached tokens attended "
        f"a step: full class {win['full_ctx_tokens_total'] / steps:.0f}, "
        f"window class {win['window_ctx_tokens_total'] / steps:.0f}; "
        f"window-class pages a decoding row "
        f"{win['window_pages_held_total'] / max(1, win['decode_rows_total']):.2f}"
        f"; pages in use now {srv.pages_in_use_by_class}")


def run_window(ctx: Dict, *, model_config: Callable, seeded_params: Callable,
               model_class, serve_for_check: Callable,
               compare_served: Callable, report_family: Callable) -> Dict:
    """``serve_deepseek.run`` with what a family brings as arguments
    (the module docstring says why it is here and not imported). The
    check comes in two halves: what it needs of the engine is served and
    taken first, then the engine and its pools go, then the comparison
    is computed beside the weights alone."""
    import gc

    import jax

    from bigdl_tpu.llm.serving import LLMServer

    say, config, mix = ctx["say"], ctx["config"], ctx["mix"]
    reh = config.get("rehearse", {}) if ctx["rehearse"] else {}
    cfg = model_config(config, reh.get("model", {}))
    engine = {**config["engine"], **reh.get("engine", {})}
    scale = float(reh.get("length_scale", 1.0))
    seconds = ctx["seconds"]

    t0 = time.perf_counter()
    with jax.default_device(ctx["devices"][0]):
        params = seeded_params(cfg, ctx["seed"] % (2 ** 31 - 1), config)
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(params))
    say(f"weights: {nbytes / 2**30:.2f} GiB of seeded bfloat16 params on "
        f"the device in {time.perf_counter() - t0:.2f} s; "
        f"{_memory(ctx['devices'][0])}")
    model = model_class(cfg, params, max_cache_len=128)
    srv = LLMServer(model, **engine).start()
    pools = [None if p is None else p.shape
             for p in jax.tree_util.tree_leaves((srv._k_pages, srv._v_pages))]
    say(f"server: LLMServer({engine}) started; pools {pools}; "
        f"{_memory(ctx['devices'][0])}")
    obs = Observer()
    try:
        _warm(srv, mix, cfg.vocab_size, scale, engine["max_batch"], say)
        if ctx["trace"]:
            _annotate_engine(srv)
        reqs = scheduled_requests(mix, ctx["seed"], seconds,
                                  cfg.vocab_size, scale)
        obs.start()
        tracks: List[Track] = []
        lateness: List[float] = []

        t_sched = time.perf_counter() + 0.05
        t_open = t_sched + mix["lead_in_s"]
        t_close = t_open + seconds
        obs.window = (t_open, t_close)

        def generate():
            for r in reqs:
                due = t_sched + r["due"]
                _sleep_until(due)
                tr = _submit(srv, obs, r, due)
                tracks.append(tr)
                lateness.append(tr.sent - due)
        sender = threading.Thread(target=generate, daemon=True,
                                  name="bench-generator")
        sender.start()

        def counters():
            return _family_counters(srv)

        _sleep_until(t_open)
        at_open = counters()
        setup_s = t_open - ctx["t_start"]
        say(f"window opens {setup_s:.2f} s after process start; "
            f"{_memory(ctx['devices'][0])}")

        traced = None
        if ctx["trace"]:
            tconf = config.get("trace", {})
            start = min(tconf.get("start_s", 3.0), seconds / 4)
            length = min(tconf.get("slice_s", 4.0), seconds / 2)
            _sleep_until(t_open + start)
            ta = time.perf_counter()
            with trace_reduce.record() as tdir:
                slice_a = counters()
                time.sleep(length)
                slice_b = counters()
            say(f"trace: {length:g} s slice from {start:g} s into the "
                f"window; start_trace took {slice_a['t'] - ta:.2f} s, "
                f"stop_trace {time.perf_counter() - slice_b['t']:.2f} s")
            traced = (tdir, _delta(slice_a, slice_b))

        _sleep_until(t_close)
        at_close = counters()
        compiles_in = ctx["compiles"].inside(t_open, t_close)
        sender.join(600)
        # drain: every request due in the window gets its allowance
        mine = [tr for tr in tracks if t_open <= tr.due < t_close]
        deadline = t_close + config.get("drain_allowance_s", 60.0)
        for tr in mine:
            if tr.req is not None:
                tr.req.done.wait(max(0.0, deadline - time.perf_counter()))
        drained = time.perf_counter() - t_close
        time.sleep(0.01)            # let the observer take the last stamps
        obs.halt.set()
        obs.join(5)

        # --- the caller's side ---------------------------------------
        failed = wrong = 0
        ttft: List[float] = []
        for tr in mine:
            req = tr.req
            if req is None or not req.done.is_set() or req.error:
                failed += 1
                continue
            toks = list(req.tokens)
            if len(toks) != tr.max_new or \
                    not all(0 <= t < cfg.vocab_size for t in toks):
                wrong += 1
            ttft.append((req.t_first_token - tr.due) * 1e3)
        gaps: List[float] = []
        tokens_in = 0
        for tr in tracks:
            if tr.req is None:
                continue
            st = tr.stamps[:tr.seen]
            gaps.extend(stats.gaps_in_window(st, t_open, t_close))
            tokens_in += int(((st >= t_open) & (st < t_close)).sum())
        samples = {"ttft": ttft, "itl": [g * 1e3 for g in gaps]}
        served_tok_s = tokens_in / seconds
        e2e = {}
        for name in ctx["wanted_e2e"]:
            if name == "setup_s":
                e2e[name] = setup_s
            elif name == "served_tok_s":
                e2e[name] = served_tok_s
            else:       # KeyError: a name this driver cannot measure
                e2e[name] = stats.named(name, samples)
        win = _delta(at_open, at_close)
        passes = max(1, win["passes"])
        say(f"window: {seconds:g} s, {len(tracks)} requests sent, "
            f"{len(mine)} due inside, {failed} failed, {wrong} wrong, "
            f"{sum(1 for t in mine if t.refused)} refused at submit; "
            f"drained {drained:.2f} s after it")
        # the gaps' quantiles around the judged one too: how steep the
        # distribution is where p95 sits says how far p95 can be trusted
        for family, qs in (("ttft", (50, 60, 70, 80, 90)),
                           ("itl", (50, 75, 90, 93, 94, 95, 96, 97, 98,
                                    99))):
            v = samples[family]
            if v:
                say(f"window: {family} ms n={len(v)} mean="
                    f"{sum(v) / len(v):.3f} " + " ".join(
                        f"p{q}={stats.percentile(v, q):.3f}" for q in qs)
                    + f" max={max(v):.3f}")
        if samples["itl"]:
            med = stats.percentile(samples["itl"], 50)
            say(f"window: {sum(g > 1.5 * med for g in samples['itl']) / len(samples['itl']):.4f}"
                f" of the gaps exceed 1.5 x the median gap (the gaps that "
                f"hold a prefill)")
        say(f"window: {tokens_in} tokens seen inside = {served_tok_s:.2f} "
            f"tokens/s")
        if lateness:
            say(f"window: generator lateness p99 "
                f"{stats.percentile(lateness, 99) * 1e3:.3f} ms, max "
                f"{max(lateness) * 1e3:.3f} ms")
        say(f"window: observer worst period {obs.worst_period * 1e3:.2f} ms")
        say(f"window: engine passes {win['passes']}, host "
            f"{win['host_seconds'] / passes * 1e3:.3f} ms/pass, fence wait "
            f"{win['stall_seconds'] / passes * 1e3:.3f} ms/pass, prefilled "
            f"{win['prefill_tokens']} prompt tokens, pass_errors "
            f"{srv.pass_errors}, programs first used inside {compiles_in}")
        report_family(say, win, cfg, srv)
        inside = [s for s in obs.samples if t_open <= s[0] < t_close]
        q = max(1, len(inside) // 4)
        quarters = [inside[i:i + q] for i in range(0, 4 * q, q)]
        backlog = [float(np.mean([w for _, w, _ in part])) if part else 0.0
                   for part in quarters]
        rows_mean = obs.decoding_area / seconds
        say(f"window: waiting for a first token, mean per quarter "
            f"{[round(b, 2) for b in backlog]}; rows decoding mean "
            f"{rows_mean:.2f} of {engine['max_batch']}; "
            f"{_memory(ctx['devices'][0])}")

        took = serve_for_check(
            srv, cfg, ctx["seed"],
            {**config["reference_check"], **reh.get("reference_check", {})})
        programs = _program_names(say)
        kernels_ok = ctx["rehearse"] or all(
            programs["pallas"].get(k, 0) > 0
            for k in config["programs_with_kernels"])
        pass_errors = srv.pass_errors
        srv.stop()
        srv = None              # the pools go with the engine
        gc.collect()
        say(f"the engine is stopped and let go: "
            f"{_memory(ctx['devices'][0])}")
        checks = compare_served(cfg, params, took, config, say)
        ok_ref = all(checks[k] for k in "dabc")
        say(f"after the reference check: {_memory(ctx['devices'][0])}")
    finally:
        if srv is not None:
            srv.stop()
        obs.halt.set()

    reduced = None
    if traced is not None:
        tdir, slice_counters = traced
        reduced = trace_reduce.collect(tdir, 1)
        reduced["slice_counters"] = slice_counters
        say(f"trace: busy {reduced['busy_s']:.4f} of "
            f"{reduced['window_s']:.4f} s")

    correct = (not failed and not wrong and ok_ref
               and kernels_ok and pass_errors == 0 and compiles_in == 0
               and bool(mine))
    if not correct:
        say(f"NOT CORRECT: failed {failed}, wrong {wrong}, "
            f"reference ok {ok_ref}, kernels ok {kernels_ok}, "
            f"pass_errors {pass_errors}, programs first used inside the "
            f"window {compiles_in}, requests due inside {len(mine)}")
    counters_out = {**win, "compiles_in_window": compiles_in,
                    "rows_decoding_mean": rows_mean,
                    "backlog_quarters": backlog,
                    "requests_in_window": len(mine)}
    return {"correct": correct, "attempted": len(mine),
            "failed": failed + wrong, "e2e": e2e, "counters": counters_out,
            "trace": reduced, "programs": programs["names"],
            "device": ctx["device"], "config": config, "model": cfg}


def run(ctx: Dict) -> Dict:
    from bigdl_tpu.llm.models.mimo import MimoForCausalLM
    return run_window(ctx, model_config=model_config,
                      seeded_params=seeded_params,
                      model_class=MimoForCausalLM,
                      serve_for_check=serve_for_check,
                      compare_served=compare_served,
                      report_family=report_family)
