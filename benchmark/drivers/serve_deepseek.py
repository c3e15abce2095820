"""The serving driver for configurations of the ``deepseek_v3`` family
(MLA latent cache, sigmoid-routed experts): one ``LLMServer`` on one
chip under the cell's open-loop traffic, measured from the caller's side.

``drivers/serve.py`` builds its model, weights and reference inside
``run()`` (Llama's), so this driver takes from it everything that is not
Llama's (``Observer``, ``Track``, ``_submit``, ``_sleep_until``,
``_warm``, ``_annotate_engine``, ``_program_names``, ``_counters``,
``_delta``) and repeats the window logic of its ``run()`` with
``DeepseekForCausalLM``, ``weights_deepseek`` and
``reference_deepseek``. What differs is marked "family"; the rest is
``serve.run`` line for line, so a repair there belongs here too
(PERF.md §7 asks a ``benchmark`` PR to give ``serve.run`` its model from
the configuration instead).

``correct`` is ``serve.py``'s (no failed or wrong request, no pass
error, no program first used inside the window, the kernel programs hold
their Mosaic calls) and, after the window, at the run's widths, a chain
from what the ENGINE served to the float32 reference. One prompt is
served while other rows are live; :class:`ServedLogits` keeps the logits
row the engine computed at its prefill and at each of its decode steps.

- (d) served against dense: those rows against the logits of the
  program's dense bfloat16 ``forward`` over the same ids (the same
  weights and arithmetic, but a contiguous cache, no page, no kernel
  for the latent attention, another tiling of the expert product):
  the root-mean-square difference of a row in units of the row's
  spread must stay under a tight limit in the median over the
  positions (the median, because a near-tie between two experts that
  rounding flips moves one position by many times the rest), the
  engine's tokens must be the argmax of its own rows, and every latent
  row the engine cached for the request, read back through its block
  table, must be the dense forward's row for that layer and position
  (another token's row, or none, is a whole row's size away; a flipped
  expert a tenth). What lives only
  in the served path (the latent kernel, the page writers and tables,
  the expanded prefill, the expert product at decode tiles) is held
  here.
- (a) served against reference: every served token within the
  configuration's tolerance of the float32 reference's maximum, in
  logit sigmas, and the engine's rows within a limit of the
  reference's rows, again in the median over the positions.
- (b) the engine's counters say ``num_experts_per_tok`` assignments for
  every token and expert layer of the whole run.
- (c) dense against reference: the reference's experts and the dense
  forward's agree on at least the configuration's share of (token,
  layer) pairs (the two streams drift apart by rounding, so the share
  is 0.95 at best), and the program's ``route`` on the very inputs the
  reference's router was given agrees on all but exact ties, with the
  weights to a part in ten thousand.

``benchmark/check_deepseek.py`` measures the floors these limits come
from and plants faults in the served program to see each one fail.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchmark import reference, reference_deepseek, stats, trace_reduce, \
    traffic, weights_deepseek
from benchmark.drivers.serve import (Observer, Track, _annotate_engine,
                                     _counters, _delta, _program_names,
                                     _sleep_until, _submit, _warm)


def model_config(config: Dict, override: Dict):
    from bigdl_tpu.llm.models.deepseek import DeepseekConfig
    return DeepseekConfig.from_hf_config({**config, **override})


def _memory(dev) -> str:
    m = dev.memory_stats() or {}
    return (f"device memory in use {m.get('bytes_in_use', 0) / 1e9:.2f} "
            f"GB, peak so far {m.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def _family_counters(srv) -> Dict[str, float]:
    """``serve._counters`` and the family's always-on step counters."""
    return {**_counters(srv), **srv.step_counters}


def scheduled_requests(mix: Dict, seed: int, seconds: float, vocab: int,
                       scale: float) -> List[Dict]:
    """Family: ``traffic.requests``, with the ORDER of sizes and gaps
    taken from the mix's ``order_seed`` where it has one, so that
    ``--seed`` draws the token ids (and the weights) and nothing else.
    A decode step of this family costs what its batch reads, so the
    order in which the generator deals long and short answers sets the
    rows decoding (19 to 27 of 32 over six seeds) and with them every
    gap: ``itl_p95_ms`` spread 4.4 to 6.3 % over seeds with the order
    drawn from ``--seed`` (PERF.md section 6, PR 27)."""
    order = mix.get("order_seed")
    reqs = traffic.requests(mix, seed if order is None else order, seconds,
                            vocab, scale)
    if order is not None:
        rng = np.random.default_rng(seed)
        for r in reqs:
            r["prompt"] = rng.integers(0, vocab, len(r["prompt"]),
                                       dtype=np.int32)
    return reqs


class ServedTap:
    """What the engine computed for ONE request (the one whose prompt
    is ``prompt``), as device arrays. ``rows``: the last-position row of
    its prefill, then its row of every decode step, in order. A decode
    step samples a token from the row before it and computes the next,
    so row ``k`` is what the engine sampled served token ``k`` from
    (and the last row is the one no token was drawn from). Taken on
    the engine thread where the engine holds them (``_finish_prefill``'s
    ``last``; ``srv._last`` right after a decode dispatch), which costs
    one small device slice a step while the tap is on and nothing when
    it is not. ``live`` is the number of rows each of those decode
    steps advanced. ``cached``: the request's pages of the latent pool,
    (layers, pages, page, width), gathered through the engine's own
    block table right after its last decode step, ``cached_len``
    positions of them written."""

    def __init__(self, srv, prompt: np.ndarray):
        import jax
        import jax.numpy as jnp
        self.rows: List = []
        self.live: List[int] = []
        self.cached, self.cached_len = None, 0
        self._srv = srv
        finish, after = srv._finish_prefill, srv._after_dispatch

        def mine(req) -> bool:
            p = req.prompt_ids
            return p.shape == prompt.shape and bool((p == prompt).all())

        def finish_prefill(i, req, row_pages, own, last, *a, **k):
            if mine(req):
                self.rows.append(last)
            return finish(i, req, row_pages, own, last, *a, **k)

        def after_dispatch(rec, t0):
            if rec.get("fn") == "llm/decode_paged":
                for i, req in rec["pairs"]:
                    if mine(req):
                        self.rows.append(srv._last[i])
                        self.live.append(len(rec["pairs"]))
                        if srv._remaining[i] == 0:      # its last step
                            self.cached_len = int(srv._lens[i])
                            pages = -(-self.cached_len // srv._page)
                            # a page at a time: a slice of the pool in
                            # its own layout, as the kernel's DMA is
                            self.cached = jnp.stack([
                                jax.lax.dynamic_index_in_dim(
                                    srv._k_pages, pid, 1, False)[:, 0]
                                for pid in srv._bt[i, :pages].tolist()],
                                axis=1)
            return after(rec, t0)

        srv._finish_prefill, srv._after_dispatch = \
            finish_prefill, after_dispatch

    def close(self):
        """Take the tap off. Returns the logits rows, (served tokens + 1,
        vocab) float32, and the cached latent rows, (layers,
        ``cached_len``, width) float32."""
        del self._srv._finish_prefill, self._srv._after_dispatch
        rows = np.stack([np.asarray(r, np.float32) for r in self.rows]) \
            if self.rows else np.zeros((0, 0), np.float32)
        if self.cached is None:
            return rows, np.zeros((0, 0, 0), np.float32)
        c = np.asarray(self.cached, np.float32)
        return rows, c.reshape(c.shape[0], -1, c.shape[-1])[
            :, :self.cached_len]


def dense_forward(cfg, params, ids):
    """The program's own dense bfloat16 forward over ``ids`` (T,):
    ``(logits (T, vocab) float32, chosen experts (expert layers, T,
    k), its contiguous latent cache (layers, T, width) float32)``."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models import deepseek
    t = len(ids)
    fwd = jax.jit(lambda p, toks: deepseek.forward(
        p, cfg, toks, deepseek.init_cache(cfg, 1, t),
        jnp.arange(t)[None], routes=True))
    logits, cache, chosen = fwd(params, jnp.asarray(ids, jnp.int32)[None])
    return (np.asarray(logits[0], np.float32), np.asarray(chosen),
            np.asarray(cache["kv"][:, 0], np.float32))


def program_router(cfg):
    """The program's router as ``router_on_reference_inputs`` wants it."""
    import jax

    from bigdl_tpu.llm.models import deepseek
    return jax.jit(lambda router, h: deepseek.route(router, h, cfg))


def row_distance(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: the root-mean-square difference of two logits rows in
    units of ``want``'s spread over the vocabulary."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)


def cached_distance(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per (layer, position): the root-mean-square difference of two
    cached latent rows in units of ``want``'s root mean square. Another
    token's row, or a row that was never written, reads 1 or more."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).mean(-1)
                   / np.maximum((want ** 2).mean(-1), 1e-30))


def serve_with_company(srv, prompt: np.ndarray, new: int, company: int,
                       vocab: int, rs) -> tuple:
    """Serve ``prompt`` for ``new`` tokens while ``company`` other
    requests decode beside it (prompts of its own prefill bucket, so no
    program is new; each outlives it). Returns ``(served tokens, the
    engine's logits row for each, live rows at each decode step, the
    latent rows the engine cached for it)``."""
    n = len(prompt)
    low = max(2, (1 << (n - 1).bit_length()) // 2 + 1)
    others = [srv.submit(
        rs.randint(0, vocab, rs.randint(low, n + 1)).astype(np.int32),
        max_new_tokens=2 * new + company) for _ in range(company)]
    # the company decodes before the prompt arrives
    deadline = time.perf_counter() + 600
    while others and time.perf_counter() < deadline and \
            not all(o.tokens or o.done.is_set() for o in others):
        time.sleep(0.005)
    tap = ServedTap(srv, prompt)
    try:
        served = srv.submit(prompt, max_new_tokens=new).get(timeout=600)
        for o in others:
            o.get(timeout=600)
    finally:
        rows, cached = tap.close()
    return served, rows[:new], tap.live, cached


LIMITS = ("served_distance_median_max", "cached_row_distance_max",
          "reference_tolerance_sigma", "reference_distance_median_max",
          "expert_agreement_min", "router_agreement_min",
          "router_weight_tolerance")


def judge(r: Dict, config: Dict) -> Dict[str, bool]:
    """The four verdicts from the readings ``reference_check`` took and
    the configuration's limits, and from nothing else (so that
    ``check_deepseek.py --rejudge`` can hold kept readings to limits
    chosen after them)."""
    lim = {k: float(config[k]) for k in LIMITS}
    return {
        "d": bool(r["rows_taken"] and r["tokens_are_argmax_of_rows"]
                  and r["rows_live_min"] >= 2
                  and r["served_distance_median"]
                  <= lim["served_distance_median_max"]
                  and r["cached_row_distance_max"]
                  <= lim["cached_row_distance_max"]),
        "a": bool(r["reference_finite"]
                  and r["margin_sigma_max"]
                  <= lim["reference_tolerance_sigma"]
                  and r["reference_distance_median"]
                  <= lim["reference_distance_median_max"]),
        "b": bool(r["token_layers"] > 0 and r["assignments"]
                  == r["experts_per_token"] * r["token_layers"]),
        "c": bool(r["same_experts"] >= lim["expert_agreement_min"]
                  and r["router_alone_share"] >= lim["router_agreement_min"]
                  and r["router_alone_weight_off"]
                  <= lim["router_weight_tolerance"])}


def reference_check(srv, cfg, params, seed: int, config: Dict, sizes: Dict,
                    say) -> Dict:
    """Family: the chain of the module's docstring. Returns the four
    verdicts under ``"d"``, ``"a"``, ``"b"``, ``"c"`` (all must hold)
    and what was compared under ``"readings"``."""
    t0 = time.perf_counter()
    n, new = sizes["prompt_tokens"], sizes["served_tokens"]
    rs = np.random.RandomState(seed % (2 ** 31))
    prompt = rs.randint(0, cfg.vocab_size, n).astype(np.int32)
    served, rows, live, cached = serve_with_company(
        srv, prompt, new, int(sizes["company"]), cfg.vocab_size, rs)
    # the last served token was fed (and cached) but nothing drawn
    # after it: the dense forward takes it too, the comparisons of
    # logits stop before it
    ids = np.concatenate([prompt, np.asarray(served, np.int32)])
    dense, dense_chosen, dense_cache = dense_forward(cfg, params, ids)
    dense = dense[n - 1:n - 1 + new]
    routing: List = []
    logits, chosen = reference_deepseek.deepseek_logits(
        cfg, params, ids[:-1], routing=routing)
    ref = logits[n - 1:n - 1 + new]
    taken = len(served) == new and rows.shape == dense.shape \
        and cached.shape == dense_cache.shape
    nothing = np.full(new, np.inf)
    dist = row_distance(rows, dense) if taken else nothing
    c_dist = cached_distance(cached, dense_cache) if taken \
        else np.full((1, 1), np.inf)
    ref_dist = row_distance(rows, ref) if taken else nothing
    m = reference.margins(ref, served) if len(served) == new else nothing
    same = reference_deepseek.same_experts(chosen, dense_chosen[:, :-1])
    r_share, w_off = reference_deepseek.router_on_reference_inputs(
        program_router(cfg), params, routing)
    c = srv.step_counters
    r = {"rows_taken": bool(taken),
         "tokens_are_argmax_of_rows": bool(
             taken and (rows.argmax(-1) == np.asarray(served)).all()),
         "rows_live_min": min(live, default=0),
         "rows_live_max": max(live, default=0),
         "served_distance_median": float(np.median(dist)),
         "served_distance_max": float(dist.max()),
         "cached_row_distance_max": float(c_dist.max()),
         "cached_row_distance_median": float(np.median(c_dist)),
         "reference_finite": bool(np.all(np.isfinite(logits))),
         "margin_sigma_max": float(m.max()),
         "margin_sigma_mean": float(m.mean()),
         "reference_distance_median": float(np.median(ref_dist)),
         "reference_distance_max": float(ref_dist.max()),
         "assignments": int(c["moe_assignments_total"]),
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
        f"{c_dist.size} latent rows the engine cached for it lie at most "
        f"{r['cached_row_distance_max']:.4f} (median "
        f"{r['cached_row_distance_median']:.4f}) of a row's size from "
        f"that forward's cache, at most "
        f"{config['cached_row_distance_max']} -> {word('d')}")
    say(f"reference: (a) served against reference: {new} served tokens "
        f"after a {n}-token prompt lie at most "
        f"{r['margin_sigma_max']:.4f} (mean {r['margin_sigma_mean']:.4f}) "
        f"logit-sigmas below the float32 reference's maximum; "
        f"{int((m == 0).sum())}/{new} are its argmax; tolerance "
        f"{config['reference_tolerance_sigma']}; the engine's rows lie "
        f"{r['reference_distance_median']:.4f} (median; max "
        f"{r['reference_distance_max']:.4f}) of a row's spread from the "
        f"reference's, at most {config['reference_distance_median_max']} "
        f"in the median -> {word('a')}")
    say(f"reference: (b) counters {r['assignments']} routed assignments "
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


def run(ctx: Dict) -> Dict:
    import jax

    from bigdl_tpu.llm.models.deepseek import DeepseekForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    say, config, mix = ctx["say"], ctx["config"], ctx["mix"]
    reh = config.get("rehearse", {}) if ctx["rehearse"] else {}
    cfg = model_config(config, reh.get("model", {}))            # family
    engine = {**config["engine"], **reh.get("engine", {})}
    scale = float(reh.get("length_scale", 1.0))
    seconds = ctx["seconds"]

    t0 = time.perf_counter()
    with jax.default_device(ctx["devices"][0]):                 # family
        params = weights_deepseek.seeded_bf16_params(
            cfg, ctx["seed"] % (2 ** 31 - 1),
            float(config["weights_back_gain"]))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(params))
    say(f"weights: {nbytes / 2**30:.2f} GiB of seeded bfloat16 params on "
        f"the device in {time.perf_counter() - t0:.2f} s; "
        f"{_memory(ctx['devices'][0])}")
    model = DeepseekForCausalLM(cfg, params, max_cache_len=128)  # family
    srv = LLMServer(model, **engine).start()
    say(f"server: LLMServer({engine}) started; pools "
        f"{[None if p is None else p.shape for p in (srv._k_pages, srv._v_pages)]}; "
        f"{_memory(ctx['devices'][0])}")
    obs = Observer()
    try:
        _warm(srv, mix, cfg.vocab_size, scale, engine["max_batch"], say)
        if ctx["trace"]:
            _annotate_engine(srv)
        reqs = scheduled_requests(mix, ctx["seed"], seconds,      # family
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

        _sleep_until(t_open)
        at_open = _family_counters(srv)
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
                slice_a = _family_counters(srv)
                time.sleep(length)
                slice_b = _family_counters(srv)
            say(f"trace: {length:g} s slice from {start:g} s into the "
                f"window; start_trace took {slice_a['t'] - ta:.2f} s, "
                f"stop_trace {time.perf_counter() - slice_b['t']:.2f} s")
            traced = (tdir, _delta(slice_a, slice_b))

        _sleep_until(t_close)
        at_close = _family_counters(srv)
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
        for family, qs in (("ttft", (50, 60, 70, 80, 90)),
                           ("itl", (50, 95, 99))):
            v = samples[family]
            if v:
                say(f"window: {family} ms n={len(v)} mean="
                    f"{sum(v) / len(v):.3f} " + " ".join(
                        f"p{q}={stats.percentile(v, q):.3f}" for q in qs)
                    + f" max={max(v):.3f}")
        if samples["itl"]:                                      # family
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
        layer_steps = max(1, win["moe_layer_steps_total"])      # family
        say(f"window: routed experts with a token "
            f"{win['moe_experts_touched_total'] / layer_steps:.2f} of "
            f"{cfg.n_routed_experts} and fullest expert "
            f"{win['moe_max_load_total'] / layer_steps:.2f} tokens per "
            f"expert layer and step; cached tokens attended "
            f"{win['latent_ctx_tokens_total'] * cfg.num_moe_layers / layer_steps:.0f} a step")
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

        checks = reference_check(                               # family
            srv, cfg, params, ctx["seed"], config,
            {**config["reference_check"], **reh.get("reference_check", {})},
            say)
        ok_ref = all(checks[k] for k in "dabc")
        say(f"after the reference check: {_memory(ctx['devices'][0])}")
        programs = _program_names(say)
        kernels_ok = ctx["rehearse"] or all(
            programs["pallas"].get(k, 0) > 0
            for k in config["programs_with_kernels"])
        pass_errors = srv.pass_errors
    finally:
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
    counters = {**win, "compiles_in_window": compiles_in,
                "rows_decoding_mean": rows_mean, "backlog_quarters": backlog,
                "requests_in_window": len(mine)}
    return {"correct": correct, "attempted": len(mine),
            "failed": failed + wrong, "e2e": e2e, "counters": counters,
            "trace": reduced, "programs": programs["names"],
            "device": ctx["device"], "config": config, "model": cfg}
