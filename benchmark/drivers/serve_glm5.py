"""The serving driver for configurations of the ``glm_moe_dsa`` kind
(GLM-5: the ``deepseek_v3`` family's latent cache with DeepSeek Sparse
Attention, a query down-projection and a share of the experts held):
one ``LLMServer`` on one chip under the cell's open-loop traffic,
measured from the caller's side. The window loop is
``serve_mimo.run_window`` with what this configuration brings.

``correct`` is ``serve.py``'s (no failed or wrong request, no pass
error, no program first used inside the window, the kernel programs
hold their Mosaic calls) and a chain from what the ENGINE served to the
float32 reference (``benchmark/reference_glm5.py``) at the window's own
sizes. The check follows ONE request of the window (:func:`check_target`:
the first due inside it whose prompt lies past the mix's median, in the
prefill bucket above it): :class:`Tap` keeps, by reference and with no
device work, the logits row the engine computed at its prefill and at
each of its first ``served_tokens`` decode steps, in the window's own
batches. After the window, that prompt with those tokens is served again
(one prefill of ``prompt + served_tokens`` positions) for
``reserved_tokens`` more while ``company`` short rows decode beside it,
and right after its last step the tap takes its pages of the index-key
pool. The engine then goes, and the reference runs ONCE over the whole
sequence, beside the weights alone (:func:`compare_served`).

- (a) logits: every token the window and the second serving drew lies
  within the configuration's tolerance of the float32 reference's
  maximum (``reference.margins``), and the engine's rows lie within a
  limit of the reference's in the median over those positions
  (``row_distance``). What lives only in the served path (the chunked
  sparse prefill, the three decode parts at the window's contexts, the
  page writers and tables) is held here.
- (b) selection: at ``probe_positions`` positions spread over the tokens
  both servings drew, and in every layer, the served decode path's own
  scoring kernel and selection (``sparse_attention.index_scores`` and
  ``dsa_select``) run over the index keys the ENGINE cached for the
  request (by the second serving's prefill and steps), with the
  reference's indexer query and head weights at that position; of the
  positions the reference selects there, the share the program selects
  (the least over the (position, layer) pairs); the position ``t``
  itself selected by both or by neither (the current token competes);
  exactly ``min(index_topk, t + 1)`` selected; and the kernel's scores
  within a limit of their float64 form over the same cached keys and
  queries (:func:`score_error`).
- (c) experts: of the experts the reference chooses for a token and
  layer (of the sequence's first ``EXPERT_PREFIX``), the program's dense
  bfloat16 forward chooses the configuration's share
  (``serve_nemotron_h.experts_in_common``), and the program's ``route``
  on the very inputs the reference's router was given agrees on all but
  exact ties, with the weights to a part in ten thousand.
- (d) the engine's books: the rows of the window's request were taken
  (in a run of the cell; ``benchmark/check_glm5.py`` serves that request
  itself, beside ``company`` rows); the served tokens are the argmax of
  the rows computed; more than ``company`` rows were live at each step
  of the second serving; the sparse counters
  (``index_ctx_tokens_total``, ``sparse_selected_tokens_total``) moved
  by what the tap saw dispatched then; the assignments computed here
  are fewer than ``num_experts_per_tok`` for every token and expert
  layer, and some.

``benchmark/check_glm5.py`` plants faults in the served program to see
each one fail.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import reference, reference_glm5, traffic, weights_glm5
from benchmark.drivers import serve_mimo
from benchmark.drivers.serve import PAGE
from benchmark.drivers.serve_deepseek import row_distance, scheduled_requests
from benchmark.drivers.serve_nemotron_h import _peak_gb, experts_in_common
from benchmark.reference_deepseek import router_on_reference_inputs

COUNTED = ("index_ctx_tokens_total", "sparse_selected_tokens_total")
# the tokens whose experts the program's dense forward is compared on
EXPERT_PREFIX = 4096


def model_config(config: Dict, override: Dict):
    from bigdl_tpu.llm.models.deepseek import DeepseekConfig
    return DeepseekConfig.from_hf_config({**config, **override})


def seeded_params(cfg, seed: int, config: Dict):
    return weights_glm5.seeded_bf16_params(
        cfg, seed, float(config["weights_back_gain"]),
        int(config["weights_router_seed"]))


def check_target(mix: Dict, seed: int, seconds: float, vocab: int,
                 scale: float, sizes: Dict) -> Optional[Dict]:
    """The request of the run's schedule the check follows: of those due
    inside the window (else in the lead-in) whose prompt is longer than
    the mix's median and leaves room, in the prefill bucket above the
    median, for ``served_tokens + reserved_tokens`` more, the shortest
    (the reference's cost grows with the square of its length), the
    earliest of equals; None where no request does. The schedule is the
    one ``run_window`` sends (``scheduled_requests``: the sizes and
    their order are the mix's, the ids ``seed``'s)."""
    reqs = scheduled_requests(mix, seed, seconds, vocab, scale)
    median = int(traffic.quantile_grid(mix["prompt"], 1, scale)[0])
    bucket = 1 << median.bit_length()
    span = int(sizes["served_tokens"]) + int(sizes["reserved_tokens"])
    fits = [r for r in reqs if median < len(r["prompt"]) <= bucket - span
            and r["max_new"] >= int(sizes["served_tokens"])]
    inside = [r for r in fits if r["due"] >= float(mix["lead_in_s"])]
    return min(inside or fits, key=lambda r: (len(r["prompt"]), r["due"]),
               default=None)


class Tap:
    """What the engine computed for ONE request (the one whose prompt is
    ``prompt``), taken on the engine thread where the engine holds it,
    by reference: no device work while the tap is on. ``rows``: the
    last-position logits row of its prefill, then its row of every
    decode step (row ``k`` is what served token ``k`` was sampled from),
    the first ``keep`` of them where ``keep`` is given. ``live``: rows
    each of those steps advanced. With ``keys``, right after its last
    step, its pages of the index-key pool, (layers, pages, page, Di),
    gathered through the engine's own table (``cached_len`` positions
    of them written), and ``seen``: over every decode dispatch while the
    tap is on, what the engine's sparse counters count."""

    def __init__(self, srv, cfg, prompt: np.ndarray, keep: int = None,
                 keys: bool = False):
        import jax
        import jax.numpy as jnp
        self.rows: List = []
        self.live: List[int] = []
        self.req = None
        self.keys, self.cached_len = None, 0
        self.seen = dict.fromkeys(COUNTED + ("steps",), 0)
        self._srv = srv
        finish, after = srv._finish_prefill, srv._after_dispatch
        lay, topk = cfg.num_hidden_layers, cfg.index_topk

        def full() -> bool:
            return keep is not None and len(self.rows) >= keep

        def finish_prefill(i, req, row_pages, own, last, *a, **k):
            q = req.prompt_ids
            if self.req is None and q.shape == prompt.shape \
                    and bool((q == prompt).all()):
                self.req = req
                self.rows.append((last, None))
            return finish(i, req, row_pages, own, last, *a, **k)

        def after_dispatch(rec, t0):
            if rec.get("fn") != "llm/decode_paged" or (
                    not keys and (self.req is None or full())):
                return after(rec, t0)
            pairs = rec["pairs"]
            if keys:
                # lens were advanced at the dispatch: a row scored its
                # cached positions and itself, ``lens`` of them
                scored = np.asarray([srv._lens[i] for i, _ in pairs])
                self.seen["index_ctx_tokens_total"] += int(scored.sum()) * lay
                self.seen["sparse_selected_tokens_total"] += int(
                    np.minimum(scored, topk).sum()) * lay
                self.seen["steps"] += 1
            for i, req in pairs:
                if req is not self.req or full():
                    continue
                self.rows.append((srv._last, i))
                self.live.append(len(pairs))
                if keys and srv._remaining[i] == 0:      # its last step
                    self.cached_len = int(srv._lens[i])
                    pages = -(-self.cached_len // srv._page)
                    self.keys = jnp.stack([
                        jax.lax.dynamic_index_in_dim(
                            srv._v_pages, pid, 1, False)[:, 0]
                        for pid in srv._bt[i, :pages].tolist()], axis=1)
            return after(rec, t0)

        srv._finish_prefill, srv._after_dispatch = \
            finish_prefill, after_dispatch

    def close(self):
        """Take the tap off and let go of the engine. Returns the logits
        rows (rows taken, vocab) float32."""
        del self._srv._finish_prefill, self._srv._after_dispatch
        self._srv = None
        if self.keys is not None:
            self.keys = np.asarray(self.keys)
        rows = [np.asarray(a, np.float32) if i is None
                else np.asarray(a[i], np.float32) for a, i in self.rows]
        self.rows = []
        return np.stack(rows) if rows else np.zeros((0, 0), np.float32)


class WindowTap:
    """A :class:`Tap` on the check's request of the window, put on the
    engine when the window's first request is sent (the warm-up's
    requests go before it): ``submit`` stands in for ``serve.py``'s
    ``_submit`` while ``run_window`` runs."""

    def __init__(self, cfg, target: Optional[Dict], keep: int):
        self.cfg, self.target, self.keep = cfg, target, keep
        self.tap: Optional[Tap] = None

    def submit(self, inner):
        def submit(srv, obs, r, due):
            if self.tap is None and self.target is not None:
                self.tap = Tap(srv, self.cfg, self.target["prompt"],
                               keep=self.keep)
            return inner(srv, obs, r, due)
        return submit


def _with_company(srv, prompt: np.ndarray, new: int, company: int,
                  lengths, vocab: int, rs) -> List[int]:
    """Serve ``prompt`` for ``new`` tokens while ``company`` short
    requests (``lengths``: the least and most tokens) decode beside it,
    each outliving it."""
    others = [srv.submit(
        rs.randint(0, vocab, rs.randint(lengths[0], lengths[1] + 1))
        .astype(np.int32), max_new_tokens=new + 2 * company + 16)
        for _ in range(company)]
    deadline = time.perf_counter() + 600
    while others and time.perf_counter() < deadline and \
            not all(o.tokens or o.done.is_set() for o in others):
        time.sleep(0.005)
    served = srv.submit(prompt, max_new_tokens=new).get(timeout=600)
    for o in others:
        o.get(timeout=600)
    return list(served)


def serve_for_check(srv, cfg, seed: int, sizes: Dict, *, mix: Dict,
                    scale: float, target: Optional[Dict],
                    window: Optional[WindowTap] = None) -> Dict:
    """The served half of the check. The first rows: the window's tap
    on ``target`` or, where the window took none (``check_glm5.py``),
    ``target`` served here for ``served_tokens`` beside ``company``
    rows. Then ``target``'s prompt and those tokens served again for
    ``reserved_tokens`` beside ``company`` rows (short prompts of the
    mix's first prefill bucket), tapped with its index keys. Everything
    the comparison wants of the engine is taken to the host, so that the
    engine and its pools can go before the float32 reference is
    computed."""
    t0 = time.perf_counter()
    new, more = int(sizes["served_tokens"]), int(sizes["reserved_tokens"])
    company = int(sizes["company"])
    rs = np.random.RandomState(seed % (2 ** 31))
    vocab = cfg.vocab_size
    first_bucket = traffic.prefill_buckets(mix, PAGE, scale)[0]
    short = (first_bucket // 2 + 1, first_bucket)

    def idle(seconds):
        deadline = time.perf_counter() + seconds
        while not srv.engine_idle() and time.perf_counter() < deadline:
            time.sleep(0.01)
    idle(300)
    if target is None:
        raise ValueError("the schedule holds no request the check can "
                         "follow")
    prompt = target["prompt"]
    tap = window.tap if window is not None else None
    from_window = tap is not None and tap.req is not None
    if from_window:
        tokens = list(tap.req.tokens)
        first_live = list(tap.live)
        first = tap.close()
    else:
        if tap is not None:
            tap.close()
        tap = Tap(srv, cfg, prompt, keep=new)
        try:
            tokens = _with_company(srv, prompt, new, company, short, vocab,
                                   rs)
            idle(600)
        finally:
            first_live = list(tap.live)
            first = tap.close()
    again = np.concatenate([prompt, np.asarray(tokens[:new], np.int32)])
    tap = Tap(srv, cfg, again, keys=True)
    before = dict(srv.step_counters)
    try:
        served = _with_company(srv, again, more, company, short, vocab, rs)
        idle(600)
        time.sleep(0.1)             # the last steps in flight drained
    finally:
        rows = tap.close()
    counted = {k: srv.step_counters[k] - before[k] for k in COUNTED}
    return {"t0": t0, "prompt": prompt, "new": new, "more": more,
            "first_tokens": tokens[:new], "first_rows": first[:new],
            "first_live": first_live, "from_window": from_window,
            "window_expected": window is not None,
            "continued": tokens[new:new + more],
            "served": served, "rows": rows[:more], "tap": tap,
            "counted": counted, "company": company,
            "counters": dict(srv.step_counters),
            "probe_positions": int(sizes["probe_positions"]),
            "peak_gb": _peak_gb()}


def probe_selection(cfg, keys: np.ndarray, positions, qi, wi):
    """The served decode path's scoring and selection over the cached
    index ``keys`` (layers, pages, page, Di) of one request: for each
    layer and each position ``t`` of ``positions``, the reference's
    query ``qi`` (layers, P, Hi, Di) and head weights ``wi`` (layers, P,
    Hi) score the keys ``< t`` through ``sparse_attention.index_scores``
    (the kernel on the TPU) and the key at ``t`` beside them, and
    ``dsa_select`` keeps ``index_topk``. Returns ``(selected (layers,
    P, S) bool, the kernel's scores (layers, P, S) float32, the queries
    as the kernel was given them (layers, P, Hi, Di))``.
    The program holds a rotated head de-interleaved (``deepseek.
    rope_interleaved``): the reference's query is laid out so too,
    which leaves every product as it was."""
    import jax.numpy as jnp

    from bigdl_tpu.llm.kernels import sparse_attention as sa
    if cfg.indexer_rope_interleave:
        r = cfg.qk_rope_head_dim
        qi = np.concatenate([qi[..., 0:r:2], qi[..., 1:r:2], qi[..., r:]],
                            -1)
    lay, pages, page, d = keys.shape
    pool = jnp.asarray(keys).reshape(lay * pages, 1, page, d)
    lens = jnp.asarray(positions, jnp.int32)
    p = len(positions)
    pmax = -(-pages // 8) * 8
    out, scored, given = [], [], []
    for l in range(lay):
        cols = np.minimum(np.arange(pmax), pages - 1) + l * pages
        bt = jnp.asarray(np.broadcast_to(cols, (p, pmax)), jnp.int32)
        q = jnp.asarray(qi[l], keys.dtype)
        w = jnp.asarray(wi[l], jnp.float32)
        scores = sa.index_scores(q, w, pool, bt, lens, page_size=page)
        own = jnp.asarray(keys[l].reshape(-1, d)[np.asarray(positions)])
        current = sa.index_score_rows(q, w, own[:, None])[:, 0]
        sel, ok = sa.dsa_select(scores, current, lens, k=cfg.index_topk)
        mask = np.zeros((p, pmax * page), bool)
        for r in range(p):
            mask[r, np.asarray(sel[r])[np.asarray(ok[r])]] = True
        out.append(mask)
        scored.append(np.asarray(scores, np.float32))
        given.append(np.asarray(q, np.float32))
    return np.stack(out), np.stack(scored), np.stack(given)


def score_error(keys: np.ndarray, positions, q, wi, scores):
    """The kernel's scores against their plain float64 form over the
    SAME cached keys and queries, positions ``s < t`` of each probed
    ``t``: the largest, over the (layer, position) pairs, of the
    root-mean-square difference in units of the plain scores' root mean
    square. Scores kept in float32 read ~1e-6, rounded to bfloat16
    ~1e-3."""
    lay, pages, page, d = keys.shape
    k = np.asarray(keys, np.float64).reshape(lay, pages * page, d)
    worst = 0.0
    for l in range(lay):
        for r, t in enumerate(positions):
            s = np.einsum("hd,sd->hs", np.asarray(q[l, r], np.float64),
                          k[l, :t])
            want = np.maximum(s, 0.0).T @ np.asarray(wi[l, r], np.float64)
            got = np.asarray(scores[l, r, :t], np.float64)
            worst = max(worst, float(np.sqrt(((got - want) ** 2).mean())
                                     / np.sqrt((want ** 2).mean())))
    return worst


def dense_choices(cfg, params, ids):
    """The experts the program's own dense bfloat16 forward chooses over
    the first ``EXPERT_PREFIX`` of ``ids`` (T,): (expert layers, T', k).
    A token's experts depend on no later token, so a prefix is compared
    as it stands; the whole sequence's dense forward does not fit beside
    the weights at the check's lengths."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models import deepseek
    ids = ids[:EXPERT_PREFIX]
    t = len(ids)
    span = -(-t // deepseek.ATTN_KEY_BLOCK) * deepseek.ATTN_KEY_BLOCK

    def fwd(p, toks):
        return deepseek.forward(p, cfg, toks,
                                deepseek.init_cache(cfg, 1, span),
                                jnp.arange(t)[None], routes=True)[2]
    return np.asarray(jax.jit(fwd)(params, jnp.asarray(ids, jnp.int32)[None]))


def program_router(cfg):
    """The program's router as ``router_on_reference_inputs`` wants it
    (looked up when called: a planted fault replaces it)."""
    import jax

    from bigdl_tpu.llm.models import deepseek
    return jax.jit(lambda router, h: deepseek.route(router, h, cfg))


VERDICTS = "dabc"

LIMITS = ("reference_tolerance_sigma", "reference_distance_median_max",
          "selection_agreement_min", "own_position_agreement_min",
          "index_score_error_max", "expert_agreement_min",
          "router_agreement_min", "router_weight_tolerance")


def judge(r: Dict, config: Dict) -> Dict[str, bool]:
    """The four verdicts from the readings ``compare_served`` took and
    the configuration's limits, and from nothing else (so that
    ``check_glm5.py --rejudge`` can hold kept readings to limits chosen
    after them)."""
    lim = {k: float(config[k]) for k in LIMITS}
    if r.get("rehearsal_widths"):
        lim.update(config["rehearse"].get("limits", {}))
    return {
        "d": bool(r["rows_taken"] and r["tokens_are_argmax_of_rows"]
                  and r["first_from_window"] == r["window_expected"]
                  and r["rows_live_min"] > r["company"]
                  and r["counters_agree"] and r["token_layers"] > 0
                  and 0 < r["assignments"]
                  < r["experts_per_token"] * r["token_layers"]),
        "a": bool(r["reference_finite"]
                  and r["margin_sigma_max"]
                  <= lim["reference_tolerance_sigma"]
                  and r["reference_distance_median"]
                  <= lim["reference_distance_median_max"]),
        "b": bool(r["selection_counts_exact"]
                  and r["selection_agreement_min"]
                  >= lim["selection_agreement_min"]
                  and r["own_position_agreement"]
                  >= lim["own_position_agreement_min"]
                  and r["index_score_error_max"]
                  <= lim["index_score_error_max"]),
        "c": bool(r["same_experts"] >= lim["expert_agreement_min"]
                  and r["router_alone_share"] >= lim["router_agreement_min"]
                  and r["router_alone_weight_off"]
                  <= lim["router_weight_tolerance"])}


def compare_served(cfg, params, took: Dict, config: Dict, say) -> Dict:
    """The comparing half: what :func:`serve_for_check` took against the
    float32 reference, computed once over the prompt, the first
    ``served_tokens`` and the second serving's tokens."""
    t0, prompt, new, more, served, rows, tap, counted = (
        took[k] for k in ("t0", "prompt", "new", "more", "served", "rows",
                          "tap", "counted"))
    first, tokens = took["first_rows"], took["first_tokens"]
    n = len(prompt)
    n2 = n + len(tokens)
    # the last served token was fed (its row and key cached) but nothing
    # drawn after it: the selections are probed up to it, the logits
    # stop before it
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32),
                          np.asarray(served, np.int32)])
    t = len(ids)
    positions = tuple(int(p) for p in np.linspace(
        n, t - 1, took["probe_positions"]).round())
    selections: List = []
    routing: List = []
    logits, chosen = reference_glm5.glm5_logits(
        cfg, params, ids, routing=routing, selections=selections,
        probe=positions)
    ref = np.concatenate([logits[n - 1:n - 1 + len(tokens)],
                          logits[n2 - 1:n2 - 1 + len(served)]])
    drawn = np.asarray(list(tokens) + list(served))
    taken = len(tokens) == new and len(served) == more \
        and first.shape[0] == new and rows.shape[0] == more \
        and tap.keys is not None and tap.cached_len == t
    got = np.concatenate([first, rows]) if taken else None
    nothing = np.full(new + more, np.inf)
    ref_dist = row_distance(got, ref) if taken else nothing
    m = reference.margins(ref, drawn) if len(drawn) == new + more \
        else nothing
    agree, exact, own, err = np.zeros((1, 1)), False, 0.0, np.inf
    if taken:
        wi = np.stack([s[2] for s in selections])
        sel, scores, q = probe_selection(
            cfg, tap.keys, positions, np.stack([s[1] for s in selections]),
            wi)
        err = score_error(tap.keys, positions, q, wi, scores)
        want = np.stack([s[0] for s in selections])
        sel = sel[..., :t]
        agree = (sel & want).sum(-1) / np.maximum(want.sum(-1), 1)
        exact = bool((sel.sum(-1) == np.minimum(
            cfg.index_topk, np.asarray(positions) + 1)[None]).all())
        # the position a query is at: selected by both or by neither
        at, rows_ = np.asarray(positions), np.arange(len(positions))
        own = float((sel[:, rows_, at] == want[:, rows_, at]).mean())
    same = experts_in_common(chosen[:, :EXPERT_PREFIX],
                             dense_choices(cfg, params, ids))
    r_share, w_off = router_on_reference_inputs(
        program_router(cfg), params, routing)
    c = took["counters"]
    cont = took["continued"]
    r = {"rows_taken": bool(taken),
         "rehearsal_widths": cfg.hidden_size != config["hidden_size"],
         "first_from_window": bool(took["from_window"]),
         "window_expected": bool(took["window_expected"]),
         "prompt_tokens": n, "sequence_tokens": t,
         "tokens_are_argmax_of_rows": bool(
             taken and (got.argmax(-1) == drawn).all()),
         "first_rows_live_min": min(took["first_live"], default=0),
         "first_rows_live_max": max(took["first_live"], default=0),
         "rows_live_min": min(tap.live, default=0),
         "rows_live_max": max(tap.live, default=0),
         "company": took["company"],
         "second_serving_continues_first": float(np.mean(
             np.asarray(cont) == np.asarray(served[:len(cont)])))
         if cont and len(served) >= len(cont) else None,
         "counters_agree": bool(tap.seen["steps"] > 0 and all(
             counted[k] == tap.seen[k] for k in counted)),
         "counted": counted, "dispatched": dict(tap.seen),
         "assignments": int(c["moe_assignments_total"]),
         "token_layers": int(c["moe_token_layers_total"]),
         "experts_per_token": int(cfg.num_experts_per_tok),
         "reference_finite": bool(np.all(np.isfinite(ref))),
         "margin_sigma_max": float(np.max(m)),
         "margin_sigma_mean": float(np.mean(m)),
         "reference_distance_median": float(np.median(ref_dist)),
         "reference_distance_max": float(np.max(ref_dist)),
         "first_distance_median": float(np.median(ref_dist[:new])),
         "second_distance_median": float(np.median(ref_dist[new:])),
         "probe_positions": list(positions),
         "selection_agreement_min": float(agree.min()),
         "selection_agreement_mean": float(agree.mean()),
         "selection_agreement_by_layer": [round(float(x), 5)
                                          for x in agree.min(1)],
         "selection_counts_exact": exact,
         "own_position_agreement": own,
         "index_score_error_max": err,
         "same_experts": float(same.mean()),
         "same_experts_by_layer": [round(float(x), 4)
                                   for x in same.mean(1)],
         "router_alone_share": r_share, "router_alone_weight_off": w_off,
         "peak_gb_served": took["peak_gb"]}
    ok = judge(r, config)

    def word(k):
        return "ok" if ok[k] else "FAILED"
    where = "the window's" if r["first_from_window"] else "the check's"
    say(f"reference: (d) the engine's books: {len(first)} logits rows of "
        f"{where} request ({r['first_rows_live_min']}-"
        f"{r['first_rows_live_max']} rows live at its steps) and "
        f"{len(rows)} of its second serving (its tokens "
        f"{'are' if r['tokens_are_argmax_of_rows'] else 'ARE NOT'} their "
        f"argmax; {r['rows_live_min']}-{r['rows_live_max']} rows live at "
        f"its steps, more than {r['company']} wanted; it continues the "
        f"first serving's tokens in {r['second_serving_continues_first']} "
        f"of them); sparse counters {counted} against {tap.seen} "
        f"dispatched; {r['assignments']} assignments computed here over "
        f"{r['token_layers']} (token, expert layer) pairs of "
        f"{r['experts_per_token']} each; peak device memory "
        f"{took['peak_gb']} GB when served -> {word('d')}")
    say(f"reference: (a) logits: after a {n}-token prompt, {new} tokens "
        f"{where} serving drew and {more} more of the second serving (a "
        f"{n2}-token prompt) lie at most {r['margin_sigma_max']:.4f} (mean "
        f"{r['margin_sigma_mean']:.4f}) logit-sigmas below the float32 "
        f"reference's maximum; {int((m == 0).sum())}/{new + more} are its "
        f"argmax; tolerance {config['reference_tolerance_sigma']}; the "
        f"engine's rows lie {r['reference_distance_median']:.5f} (median; "
        f"first serving {r['first_distance_median']:.5f}, second "
        f"{r['second_distance_median']:.5f}; max "
        f"{r['reference_distance_max']:.4f}) of a row's spread from the "
        f"reference's, at most {config['reference_distance_median_max']} "
        f"-> {word('a')}")
    say(f"reference: (b) selection: at positions {list(positions)} of "
        f"the {t} and in every layer the served scoring kernel and "
        f"selection over the engine's cached index keys keep "
        f"{r['selection_agreement_min']:.5f} at the least (mean "
        f"{r['selection_agreement_mean']:.5f}; by layer "
        f"{r['selection_agreement_by_layer']}) of what the reference "
        f"selects, at least {config['selection_agreement_min']}; the "
        f"position a query is at selected alike in {own:.4f} of the "
        f"pairs, at least {config['own_position_agreement_min']}; exactly "
        f"min({cfg.index_topk}, t + 1) selected: {exact}; the kernel's "
        f"scores lie {err:.2e} from their float64 form over the same keys "
        f"and queries, at most {config['index_score_error_max']} -> "
        f"{word('b')}")
    say(f"reference: (c) experts: the program's dense forward chooses "
        f"{r['same_experts']:.4f} of the reference's experts over "
        f"{same.size} (token, layer) pairs (by layer "
        f"{r['same_experts_by_layer']}), at least "
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
    lay = cfg.num_hidden_layers
    scored = win["index_ctx_tokens_total"]
    kept = win["sparse_selected_tokens_total"]
    say(f"window: decode steps {steps:.0f}; cached tokens a step "
        f"{win['latent_ctx_tokens_total'] / steps:.0f}; scored a step and "
        f"layer {scored / steps / lay:.0f}, selected "
        f"{kept / steps / lay:.0f} ({100 * kept / max(1, scored):.2f} %); "
        f"index keys read {win['index_key_bytes_total'] / steps / 1e9:.3f}"
        f" GB and selected rows "
        f"{win['sparse_latent_bytes_total'] / steps / 1e9:.3f} GB a step; "
        f"held experts with a token "
        f"{win['moe_experts_touched_total'] / layer_steps:.2f} of "
        f"{cfg.routed_held} (of {cfg.n_routed_experts} routed over), "
        f"fullest {win['moe_max_load_total'] / layer_steps:.2f} tokens, per "
        f"expert layer and step; assignments computed here "
        f"{win['moe_assignments_total']}; prefill index pairs "
        f"{win['prefill_index_pairs_total']}; pages in use now "
        f"{srv.pages_in_use_by_class}")


def check_sizes(config: Dict, rehearse: bool) -> Dict:
    """The check's sizes: ``reference_check``, the rehearsal's over it."""
    reh = config.get("rehearse", {}) if rehearse else {}
    return {**config["reference_check"], **reh.get("reference_check", {})}


def run(ctx: Dict) -> Dict:
    """``run_window`` with the check's tap on the window: the server is
    built inside it, so the tap goes on at the window's first send
    (``serve_mimo``'s ``_submit``, stood in for while the window runs)."""
    from unittest import mock

    from bigdl_tpu.llm.models.deepseek import DeepseekForCausalLM
    config, mix = ctx["config"], ctx["mix"]
    reh = config.get("rehearse", {}) if ctx["rehearse"] else {}
    cfg = model_config(config, reh.get("model", {}))
    scale = float(reh.get("length_scale", 1.0))
    sizes = check_sizes(config, ctx["rehearse"])
    target = check_target(mix, ctx["seed"], ctx["seconds"], cfg.vocab_size,
                          scale, sizes)
    window = WindowTap(cfg, target, int(sizes["served_tokens"]))
    with mock.patch.object(serve_mimo, "_submit",
                           window.submit(serve_mimo._submit)):
        return serve_mimo.run_window(
            ctx, model_config=model_config, seeded_params=seeded_params,
            model_class=DeepseekForCausalLM,
            serve_for_check=functools.partial(
                serve_for_check, mix=mix, scale=scale, target=target,
                window=window),
            compare_served=compare_served, report_family=report_family)
