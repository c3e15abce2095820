"""The serving driver for configurations of the ``nemotron_h`` family (one
mixer a layer: Mamba-2 layers whose state a slot holds, attention layers
that cache every token in pages, LatentMoE layers of which the chip
holds a share): one ``LLMServer`` on one chip under the cell's open-loop
traffic, measured from the caller's side. The window loop is
``serve_mimo.run_window`` with what this family brings; a request holds
pages and a state slot at once.

``correct`` is ``serve.py``'s (no failed or wrong request, no pass
error, no program first used inside the window, the kernel programs
hold their Mosaic calls) and, after the window, at the run's widths, a
chain from what the ENGINE served to the float32 reference. One prompt
of several prefill chunks is served while a dozen other rows are live,
seated in a slot another request has left; :class:`Tap` keeps the
logits row the engine computed at its prefill and at each of its decode
steps, both arrays of the state class and the pages the engine holds
for it after its last step, and the answer of the served decode
dispatch to one probe token over that state. That is the check's served
half (:func:`serve_for_check`); a run then lets the engine go and
computes the comparisons (:func:`compare_served`) beside the weights
alone, so that ``memory_peak_bytes`` is what the served state held.

- (a) logits: every served token within the configuration's tolerance
  of the float32 reference's maximum (``reference.margins``) and the
  engine's rows within a limit of the reference's in the median
  (``row_distance``). The reference computes the dual form over the
  same ids: no state, no chunk, no kernel. And once more for what a long
  prompt cannot show: right after the long request a **short** one (256
  tokens) is seated where one of the company's states and windows lie,
  its logits rows are held to the same limit and its state, after its
  272 positions, to the long request's (a window left behind poisons
  three positions: a hundredth of a state that young, nothing of the
  long request's; a state left behind under decays of 0.9 to 0.999 is
  still there after 256).
- (b) state: what the engine holds for the request after its last step,
  every Mamba-2 layer, against ``sum_s exp(a sum dt) dt_s x_s B_s^T``
  built directly by the reference from its own ``x``, ``B`` and ``dt``,
  root-mean-square difference in units of the reference's root mean
  square, the largest of the layers; the convolution's window likewise
  against the reference's last three inputs; the K and V rows the
  attention layer cached against the reference's keys and values.
- (c) probe: one random token through the served decode dispatch
  (``kernels.ssm.ssm_decode``: the kernel, the live-row walk, the
  in-place update) over a copy of the request's first-layer state,
  against the float64 recurrence on the same state.
- (d) the engine's own books: the served tokens are the argmax of the
  rows it computed; at least ``company`` other rows were live at each
  of its steps; its slot had held another request before (the ledger's
  count), and so had the short request's; the counters
  (``ssm_rows_total``, ``kv_ctx_tokens_total``, ``decode_rows_total``,
  ``state_slots_held_total``) moved by what the tap saw dispatched; and
  assignments computed here and left to the other shares add up to
  ``num_experts_per_tok`` for every token and expert layer of the run.
- (e) experts: of the experts the reference chooses for a token and
  layer, the program's dense forward chooses the configuration's share
  (:func:`experts_in_common`), and
  the program's ``route`` on the very inputs the reference's router was
  given agrees on all but exact ties, with the weights to a part in ten
  thousand.

``benchmark/check_nemotron_h.py`` plants faults in the served program
to see each one fail.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import reference, reference_nemotron_h, weights_nemotron_h
from benchmark.drivers import serve_mimo
from benchmark.drivers.serve_brumby import _longest_phases, rel_rms
from benchmark.drivers.serve_deepseek import cached_distance, row_distance
from benchmark.reference_mimo import router_on_reference_inputs

def _peak_gb() -> float:
    """The device's peak memory so far, GB (0 where it keeps none)."""
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return round(stats.get("peak_bytes_in_use", 0) / 1e9, 2)


COUNTED = ("ssm_rows_total", "kv_ctx_tokens_total", "decode_rows_total",
           "state_slots_held_total")


def model_config(config: Dict, override: Dict):
    from bigdl_tpu.llm.models.nemotron_h import NemotronHConfig
    return NemotronHConfig.from_hf_config({**config, **override})


def seeded_params(cfg, seed: int, config: Dict):
    return weights_nemotron_h.seeded_bf16_params(
        cfg, seed, float(config["weights_back_gain"]),
        float(config["weights_dt_spread"]), config["weights_decay"],
        int(config["weights_router_seed"]))


class Tap:
    """What the engine computed for ONE request (the one whose prompt is
    ``prompt``), as device arrays, taken on the engine thread where the
    engine holds them. ``rows``: the last-position logits row of its
    prefill, then its row of every decode step (row ``k`` is what served
    token ``k`` was sampled from). ``live``: rows each of those steps
    advanced. ``slot`` and ``seatings``: where it was seated and how
    often that slot had been seated by then. Right after its last step:
    ``state`` and ``window``, its row of the state class's two arrays,
    every layer; ``cached``, its pages of the K and the V pool gathered
    through the engine's own table; ``probe``, the served decode
    dispatch on one random token over a copy of the first layer's
    state. ``seen`` sums, over every decode dispatch while the tap is
    on, what the engine's counters count."""

    def __init__(self, srv, cfg, prompt: np.ndarray, seed: int,
                 keep_state: bool = True):
        import jax
        import jax.numpy as jnp
        self.rows: List = []
        self.live: List[int] = []
        self.slot, self.seatings = -1, 0
        self.state = self.window = self.cached = self.probe = None
        self.state_len = 0
        self.peaks: Dict[str, float] = {}
        self.seen = dict.fromkeys(COUNTED + ("steps",), 0)
        self._srv = srv
        finish, after = srv._finish_prefill, srv._after_dispatch
        (ledger,) = srv._states
        rs = np.random.RandomState(seed % (2 ** 31))
        heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n = cfg.n_groups, cfg.ssm_state_size
        # the probe's token: unit-spread x, B and C (as the model's are
        # after the convolution's silu), a time step of 0.01
        self.x = rs.randn(heads, p).astype(np.float32)
        self.bm = rs.randn(g, n).astype(np.float32)
        self.cm = rs.randn(g, n).astype(np.float32)
        self.dt = np.full((heads,), 0.01, np.float32)

        def mine(req) -> bool:
            q = req.prompt_ids
            return q.shape == prompt.shape and bool((q == prompt).all())

        def finish_prefill(i, req, row_pages, own, last, *a, **k):
            if mine(req):
                self.rows.append(last)
                self.slot, self.seatings = i, ledger.seatings[i]
            return finish(i, req, row_pages, own, last, *a, **k)

        @jax.jit
        def probed(state, x, bm, cm, dt, a_log, d_skip):
            from bigdl_tpu.llm.kernels import ssm
            # rows: [trash, the request's]; batch: [a dead row, the live]
            two = lambda v: jnp.stack([jnp.zeros_like(v), v])
            y, s = ssm.ssm_decode(
                two(state), two(x), two(bm), two(cm), two(dt),
                -jnp.exp(a_log), d_skip, jnp.asarray([0, 1], jnp.int32),
                jnp.asarray([False, True]))
            return y[1], s[1]

        def gather(pool, pids):
            return jnp.stack([jax.lax.dynamic_index_in_dim(
                pool, pid, 1, False) for pid in pids], axis=1)

        def after_dispatch(rec, t0):
            if rec.get("fn") == "llm/decode_paged":
                pairs = rec["pairs"]
                self.seen["ssm_rows_total"] += len(pairs)
                self.seen["decode_rows_total"] += len(pairs)
                self.seen["kv_ctx_tokens_total"] += int(sum(
                    srv._lens[i] - 1 for i, _ in pairs))
                self.seen["state_slots_held_total"] += \
                    ledger.slots_in_use()
                self.seen["steps"] += 1
                for i, req in pairs:
                    if mine(req):
                        self.rows.append(srv._last[i])
                        self.live.append(len(pairs))
                        if keep_state and srv._remaining[i] == 0:
                            # right after its last step
                            self.state_len = int(srv._lens[i])
                            row = int(ledger.rows[i, 0])
                            self.peaks["before the tap's copies"] = _peak_gb()
                            self.state = srv._k_pages[1][:, row]
                            self.window = srv._v_pages[1][:, row]
                            self.peaks["state and window taken"] = _peak_gb()
                            pages = -(-self.state_len // srv._page)
                            pids = srv._bt[i, :pages].tolist()
                            self.cached = (gather(srv._k_pages[0], pids),
                                           gather(srv._v_pages[0], pids))
                            self.peaks["pages gathered"] = _peak_gb()
                            lp = srv.model.params["layers"][
                                cfg.layers_of("M")[0]]
                            self.probe = probed(
                                self.state[0], *(jnp.asarray(v) for v in (
                                    self.x, self.bm, self.cm, self.dt)),
                                lp["A_log"], lp["D"])
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

    def cached_rows(self, page: int):
        """``(keys (L, n, hkv, d), values (L, n, hkv, d))`` float32 of
        the positions the request holds."""
        if self.cached is None:
            return None
        out = []
        for pool in self.cached:            # (L, pages, hkv, page, d)
            c = np.asarray(pool, np.float32)
            lc, pages, hkv, _, d = c.shape
            out.append(c.transpose(0, 1, 3, 2, 4).reshape(
                lc, pages * page, hkv, d)[:, :self.state_len])
        return tuple(out)


def probe_distance(tap: Tap, lp, state_ref: np.ndarray) -> float:
    """The probe's answer against the float64 recurrence of the probe's
    token on the REFERENCE's first-layer state: root-mean-square
    difference in units of the answer's root mean square."""
    if tap.probe is None:
        return float("inf")
    f64 = np.float64
    s = np.asarray(state_ref, f64)                          # (H, P, N)
    hpg = s.shape[0] // tap.bm.shape[0]
    a = -np.exp(np.asarray(lp["A_log"], f64))
    dt, x = tap.dt.astype(f64), tap.x.astype(f64)
    bh = np.repeat(tap.bm.astype(f64), hpg, axis=0)
    ch = np.repeat(tap.cm.astype(f64), hpg, axis=0)
    s1 = np.exp(dt * a)[:, None, None] * s \
        + (dt[:, None] * x)[..., None] * bh[:, None, :]
    want = np.einsum("hpn,hn->hp", s1, ch) \
        + np.asarray(lp["D"], f64)[:, None] * x
    return rel_rms(np.asarray(tap.probe[0]), want)


def serve_tapped(srv, cfg, prompt: np.ndarray, new: int, company: int,
                 rs, seed: int):
    """``serve_brumby.serve_tapped`` with this family's tap: every slot
    is given a short request first, all alive at once (so that whichever
    slot the prompt is seated in holds another's state and window), then
    ``prompt`` is served for ``new`` tokens while ``company`` others decode
    beside it (prompts of its own prefill bucket, so no program is new;
    each outlives it). The tap goes on while the engine is idle.
    Returns ``(served tokens, the engine's logits rows, the tap, the
    engine's step counters when the tap went on)``."""
    n, vocab = len(prompt), cfg.vocab_size
    low = max(2, (1 << (n - 1).bit_length()) // 2 + 1)

    def idle(seconds):
        deadline = time.perf_counter() + seconds
        while not srv.engine_idle() and time.perf_counter() < deadline:
            time.sleep(0.01)
    idle(300)
    # eight at a time, each waited for until its first token: a prefill
    # dispatched takes its workspace at once, and 64 of them in flight
    # together held 2.2 GB that nothing in the window ever holds; long
    # enough answers that all the slots are seated at once
    short, fill, group = min(256, n), [], 8
    for _ in range(0, srv.max_batch, group):
        some = [srv.submit(rs.randint(0, vocab, short).astype(np.int32),
                           max_new_tokens=40 * srv.max_batch // group)
                for _ in range(group)]
        deadline = time.perf_counter() + 300
        while time.perf_counter() < deadline and \
                not all(f.tokens or f.done.is_set() for f in some):
            time.sleep(0.005)
        fill += some
    for f in fill:
        f.get(timeout=600)
    idle(300)
    tap = Tap(srv, cfg, prompt, seed)
    tap.peaks["every slot seated once"] = _peak_gb()
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
        idle(600)
        time.sleep(0.1)             # the last steps in flight drained
    finally:
        rows = tap.close()
    tap.peaks["the request and its company served"] = _peak_gb()
    return served, rows[:new], tap, before


def serve_alone(srv, cfg, n: int, new: int, rs, seed: int) -> Dict:
    """One request of ``n`` tokens served alone for ``new``: it is
    seated in the first free slot, where another request's state and
    window lie. Returns its prompt, tokens, logits rows, slot, that
    slot's seating number and the state it holds after its last step."""
    prompt = rs.randint(0, cfg.vocab_size, n).astype(np.int32)
    tap = Tap(srv, cfg, prompt, seed)
    try:
        served = srv.submit(prompt, max_new_tokens=new).get(timeout=600)
        deadline = time.perf_counter() + 60
        while not srv.engine_idle() and time.perf_counter() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
    finally:
        rows = tap.close()
    return {"prompt": prompt, "served": served, "rows": rows[:new],
            "slot": tap.slot, "seatings": tap.seatings,
            "state_len": tap.state_len, **_to_host(tap)}


def _to_host(tap: Tap) -> Dict:
    """The tap's state and window as host arrays (None where it took
    none), and the tap let go of them."""
    out = {"state": None if tap.state is None
           else np.asarray(tap.state, np.float32),
           "window": None if tap.window is None
           else np.asarray(tap.window, np.float32)}
    if tap.probe is not None:
        tap.probe = tuple(np.asarray(a, np.float32) for a in tap.probe)
    tap.state = tap.window = None
    return out


LIMITS = ("reference_tolerance_sigma", "reference_distance_median_max",
          "state_distance_max", "state_first_layer_distance_max",
          "tiny_state_first_layer_distance_max",
          "window_distance_max", "window_first_layer_distance_max",
          "reference_cached_distance_median_max", "probe_distance_max",
          "expert_agreement_min", "router_agreement_min",
          "router_weight_tolerance")


def judge(r: Dict, config: Dict) -> Dict[str, bool]:
    """The five verdicts from the readings ``compare_served`` took and
    the configuration's limits, and from nothing else (so that
    ``check_nemotron_h.py --rejudge`` can hold kept readings to limits
    chosen after them)."""
    lim = {k: float(config[k]) for k in LIMITS}
    if r.get("rehearsal_widths"):
        # a dozen positions at tiny widths, 4 experts a token: one
        # expert chosen otherwise is a fifth of a young state
        lim.update(config["rehearse"].get("limits", {}))
    return {
        "d": bool(r["rows_taken"] and r["tokens_are_argmax_of_rows"]
                  and r["rows_live_min"] > r["company"]
                  and r["slot_seatings"] >= 2
                  and r["short_slot_seatings"] >= 2
                  and r["tiny_slot_seatings"] >= 2
                  and r["counters_agree"]
                  and r["token_layers"] > 0
                  and r["assignments"] + r["assignments_elsewhere"]
                  == r["experts_per_token"] * r["token_layers"]),
        "a": bool(r["reference_finite"]
                  and r["margin_sigma_max"]
                  <= lim["reference_tolerance_sigma"]
                  and r["reference_distance_median"]
                  <= lim["reference_distance_median_max"]
                  and r["short_reference_distance_median"]
                  <= lim["reference_distance_median_max"]),
        "b": bool(r["state_distance_max"] <= lim["state_distance_max"]
                  and r["short_state_distance_max"]
                  <= lim["state_distance_max"]
                  and r["state_first_layer_distance"]
                  <= lim["state_first_layer_distance_max"]
                  and r["tiny_state_first_layer_distance"]
                  <= lim["tiny_state_first_layer_distance_max"]
                  and r["window_distance_max"] <= lim["window_distance_max"]
                  and r["window_first_layer_distance"]
                  <= lim["window_first_layer_distance_max"]
                  and r["reference_cached_distance_median"]
                  <= lim["reference_cached_distance_median_max"]),
        "c": bool(r["probe_distance"] <= lim["probe_distance_max"]),
        "e": bool(r["same_experts"] >= lim["expert_agreement_min"]
                  and r["router_alone_share"] >= lim["router_agreement_min"]
                  and r["router_alone_weight_off"]
                  <= lim["router_weight_tolerance"])}


VERDICTS = "dabce"


def serve_for_check(srv, cfg, seed: int, sizes: Dict) -> Dict:
    """The served half of the check: the prompt served beside its
    company with the tap on, and everything the comparison wants of the
    engine taken to the host, so that the engine and its state can go
    before the float32 reference is computed beside the weights."""
    t0 = time.perf_counter()
    start_gb = _peak_gb()
    n, new = sizes["prompt_tokens"], sizes["served_tokens"]
    rs = np.random.RandomState(seed % (2 ** 31))
    prompt = rs.randint(0, cfg.vocab_size, n).astype(np.int32)
    served, rows, tap, before = serve_tapped(
        srv, cfg, prompt, new, int(sizes["company"]), rs, seed)
    counted = {k: srv.step_counters[k] - before[k] for k in COUNTED}
    took = {"t0": t0, "prompt": prompt, "new": new, "served": served,
            "rows": rows, "tap": tap, "counted": counted,
            "company": int(sizes["company"]),
            "cached": tap.cached_rows(srv._page), **_to_host(tap),
            "short": serve_alone(
                srv, cfg, int(sizes.get("short_prompt_tokens", 256)),
                int(sizes.get("short_served_tokens", 16)), rs, seed + 1),
            "tiny": serve_alone(
                srv, cfg, int(sizes.get("tiny_prompt_tokens", 6)),
                int(sizes.get("tiny_served_tokens", 2)), rs, seed + 2),
            "counters": dict(srv.step_counters)}
    tap.cached = None
    took["peaks"] = {"at the check's start": start_gb, **tap.peaks,
                     "the short and the tiny request served": _peak_gb()}
    return took


def dense_choices(cfg, params, ids):
    """The experts the program's own dense bfloat16 forward chooses over
    ``ids`` (T,): ``[(T, k) an expert layer]``."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models import nemotron_h as nh
    t = len(ids)

    def fwd(p, toks):
        _, _, chosen = nh.forward(p, cfg, toks, nh.init_cache(cfg, 1, t),
                                  jnp.arange(t)[None], routes=True)
        return chosen
    return [np.asarray(c) for c in jax.jit(fwd)(
        params, jnp.asarray(ids, jnp.int32)[None])]


def experts_in_common(a, b) -> np.ndarray:
    """(expert layers, T): the share of the experts a token chose in
    ``a`` that it also chose in ``b``, lists of (T, k) a layer. With 22
    of 512 chosen the 22nd and the 23rd score lie close for most
    tokens, so the two SETS are rarely the same and their overlap is
    what tells a drifted stream from a wrong one. 0 where the two chose
    another number of experts."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return np.zeros(a.shape[:2])
    return (a[..., :, None] == b[..., None, :]).any(-1).mean(-1)


def program_router(cfg):
    """The program's router as ``router_on_reference_inputs`` wants it
    (looked up when called: a planted fault replaces it)."""
    import jax

    from bigdl_tpu.llm.models import nemotron_h as nh
    return jax.jit(lambda router, h: nh.route(router, h, cfg))


def compare_served(cfg, params, took: Dict, config: Dict, say) -> Dict:
    """The comparing half: what :func:`serve_for_check` took against the
    float32 reference."""
    t0, prompt, new, served, rows, tap, counted = (
        took[k] for k in ("t0", "prompt", "new", "served", "rows", "tap",
                          "counted"))
    n = len(prompt)
    # the last served token was fed (and folded into the state, its K
    # and V cached) but nothing drawn after it: the state is compared
    # over all of ids, the logits stop before it
    ids = np.concatenate([prompt, np.asarray(served, np.int32)])
    ref_rows: List = []
    routing: List = []
    ref, chosen = reference_nemotron_h.nemotron_h_logits(
        cfg, params, ids, routing=routing, rows=ref_rows, last=new + 1)
    ref = ref[:new]
    taken = len(served) == new and rows.shape == ref.shape \
        and took["state"] is not None and tap.state_len == len(ids)
    nothing = np.full(new, np.inf)
    ref_dist = row_distance(rows, ref) if taken else nothing
    m = reference.margins(ref, served) if len(served) == new else nothing
    s_dist, w_dist, c_dist, probe = [np.inf], [np.inf], [np.inf], np.inf
    if taken:
        held = [r for r in ref_rows if r[0] == "M"]
        s_dist = [rel_rms(took["state"][i], s)
                  for i, (_, s, _) in enumerate(held)]
        w_dist = [rel_rms(took["window"][i], w)
                  for i, (_, _, w) in enumerate(held)]
        keys, values = took["cached"]
        c_dist = [float(np.median(cached_distance(
            np.concatenate([keys[i], values[i]], -1).reshape(len(ids), -1),
            np.concatenate([k, v], -1).reshape(len(ids), -1))))
            for i, (_, k, v) in enumerate(
                r for r in ref_rows if r[0] == "*")]
        probe = probe_distance(
            tap, params["layers"][cfg.layers_of("M")[0]], held[0][1])
    short = took["short"]
    ids2 = np.concatenate([short["prompt"],
                           np.asarray(short["served"], np.int32)])
    new2 = len(short["served"])
    rows2: List = []
    ref2, _ = reference_nemotron_h.nemotron_h_logits(
        cfg, params, ids2, rows=rows2, last=new2 + 1)
    ref2 = ref2[:new2]
    short_dist = row_distance(short["rows"], ref2) \
        if short["rows"].shape == ref2.shape else np.full(1, np.inf)
    s2_dist = [np.inf]
    if short["state"] is not None and short["state_len"] == len(ids2):
        s2_dist = [rel_rms(short["state"][i], s) for i, (_, s, _) in
                   enumerate(r for r in rows2 if r[0] == "M")]
    tiny = took["tiny"]
    ids3 = np.concatenate([tiny["prompt"],
                           np.asarray(tiny["served"], np.int32)])
    rows3: List = []
    reference_nemotron_h.nemotron_h_logits(cfg, params, ids3, rows=rows3,
                                           last=1)
    tiny_first = np.inf
    if tiny["state"] is not None and tiny["state_len"] == len(ids3):
        tiny_first = rel_rms(tiny["state"][0], rows3[0][1])
    same = experts_in_common(chosen, dense_choices(cfg, params, ids))
    r_share, w_off = router_on_reference_inputs(
        program_router(cfg), params, routing)
    c = took["counters"]
    r = {"rows_taken": bool(taken),
         "rehearsal_widths": cfg.hidden_size != config["hidden_size"],
         "tokens_are_argmax_of_rows": bool(
             taken and (rows.argmax(-1) == np.asarray(served)).all()),
         "rows_live_min": min(tap.live, default=0),
         "rows_live_max": max(tap.live, default=0),
         "company": took["company"],
         "slot": tap.slot, "slot_seatings": tap.seatings,
         "counters_agree": bool(tap.seen["steps"] > 0 and all(
             counted[k] == tap.seen[k] for k in counted)),
         "counted": counted, "dispatched": dict(tap.seen),
         "assignments": int(c["moe_assignments_total"]),
         "assignments_elsewhere": int(c["moe_assignments_elsewhere_total"]),
         "token_layers": int(c["moe_token_layers_total"]),
         "experts_per_token": int(cfg.num_experts_per_tok),
         "reference_finite": bool(np.all(np.isfinite(ref))),
         "margin_sigma_max": float(np.max(m)),
         "margin_sigma_mean": float(np.mean(m)),
         "reference_distance_median": float(np.median(ref_dist)),
         "reference_distance_max": float(np.max(ref_dist)),
         "short_reference_distance_median": float(np.median(short_dist)),
         "short_reference_distance_first": float(short_dist[0]),
         "short_slot": short["slot"],
         "short_slot_seatings": short["seatings"],
         "state_distance_max": float(np.max(s_dist)),
         "state_distance_by_layer": [round(x, 5) for x in s_dist],
         "short_state_distance_max": float(np.max(s2_dist)),
         "state_first_layer_distance": float(s_dist[0]),
         "tiny_state_first_layer_distance": float(tiny_first),
         "tiny_slot_seatings": tiny["seatings"],
         "window_first_layer_distance": float(w_dist[0]),
         "window_distance_max": float(np.max(w_dist)),
         "window_distance_by_layer": [round(x, 5) for x in w_dist],
         "reference_cached_distance_median": float(np.max(c_dist)),
         "probe_distance": float(probe),
         "same_experts": float(same.mean()),
         "same_experts_by_layer": [round(float(x), 3)
                                   for x in same.mean(1)],
         "router_alone_share": r_share, "router_alone_weight_off": w_off}
    ok = judge(r, config)

    def word(k):
        return "ok" if ok[k] else "FAILED"
    say(f"reference: (d) the engine's books: {len(rows)} logits rows for "
        f"the served request (its tokens "
        f"{'are' if r['tokens_are_argmax_of_rows'] else 'ARE NOT'} their "
        f"argmax; {r['rows_live_min']}-{r['rows_live_max']} rows live at "
        f"its steps, more than {r['company']} wanted), seated in slot "
        f"{r['slot']} at that slot's seating no. {r['slot_seatings']} (2 "
        f"or more: another request's state was there); counters "
        f"{counted} against {tap.seen} dispatched; {r['assignments']} "
        f"assignments computed here + {r['assignments_elsewhere']} left "
        f"to the other shares over {r['token_layers']} (token, expert "
        f"layer) pairs = {r['experts_per_token']} each; peak device "
        f"memory, GB, by stage of the served half: {took['peaks']} -> "
        f"{word('d')}")
    say(f"reference: (a) logits: {new} served tokens after a {n}-token "
        f"prompt lie at most {r['margin_sigma_max']:.4f} (mean "
        f"{r['margin_sigma_mean']:.4f}) logit-sigmas below the float32 "
        f"reference's maximum; {int((m == 0).sum())}/{new} are its "
        f"argmax; tolerance {config['reference_tolerance_sigma']}; the "
        f"engine's rows lie {r['reference_distance_median']:.4f} (median; "
        f"max {r['reference_distance_max']:.4f}) of a row's spread from "
        f"the reference's, at most "
        f"{config['reference_distance_median_max']}; a "
        f"{len(short['prompt'])}-token request seated after it in slot "
        f"{r['short_slot']} (seating no. {r['short_slot_seatings']}): its "
        f"{new2} rows lie {r['short_reference_distance_median']:.4f} "
        f"(the first {r['short_reference_distance_first']:.4f}) from the "
        f"reference's, the same limit -> {word('a')}")
    say(f"reference: (b) state: what the engine holds after "
        f"{tap.state_len} positions lies {r['state_distance_max']:.5f} "
        f"(the largest of the layers {r['state_distance_by_layer']}) of "
        f"its size from the sum the reference builds, the short request's "
        f"after {short['state_len']} positions "
        f"{r['short_state_distance_max']:.5f}, each at most "
        f"{config['state_distance_max']}; the first layer's "
        f"{r['state_first_layer_distance']:.5f} and, after a "
        f"{len(tiny['prompt'])}-token request's {tiny['state_len']} "
        f"positions (seating no. {r['tiny_slot_seatings']}), "
        f"{r['tiny_state_first_layer_distance']:.5f}, at most "
        f"{config['state_first_layer_distance_max']} and "
        f"{config['tiny_state_first_layer_distance_max']}; the "
        f"convolution's "
        f"window {r['window_distance_max']:.5f} "
        f"({r['window_distance_by_layer']}), at most "
        f"{config['window_distance_max']}, the first layer's at most "
        f"{config['window_first_layer_distance_max']}; the cached K and V "
        f"rows {r['reference_cached_distance_median']:.5f} in the median, "
        f"at most {config['reference_cached_distance_median_max']} -> "
        f"{word('b')}")
    say(f"reference: (c) probe: one random token through the served "
        f"decode dispatch over the first Mamba-2 layer's state lies "
        f"{r['probe_distance']:.5f} from the float64 recurrence on the "
        f"reference's state, at most {config['probe_distance_max']} -> "
        f"{word('c')}")
    say(f"reference: (e) experts: the program's dense forward chooses "
        f"{r['same_experts']:.4f} of the reference's experts over "
        f"{same.size} (token, layer) pairs (by layer "
        f"{r['same_experts_by_layer']}), at least "
        f"{config['expert_agreement_min']}; the program's router on the "
        f"reference's router inputs for {r_share:.5f} (at least "
        f"{config['router_agreement_min']}) with weights within "
        f"{w_off:.2e} (at most {config['router_weight_tolerance']}) -> "
        f"{word('e')}; {time.perf_counter() - t0:.2f} s")
    # run_window asks for "d", "a", "b", "c": (e) rides with (c)
    return {**ok, "c": ok["c"] and ok["e"], "readings": r}


def report_family(say, win: Dict, cfg, srv) -> None:
    """The family's line of the window's report."""
    layer_steps = max(1, win["moe_layer_steps_total"])
    steps = layer_steps / cfg.num_moe_layers
    rows = max(1, win["decode_rows_total"])
    say(f"window: rows a decode step {win['ssm_rows_total'] / steps:.2f}; "
        f"state moved {win['ssm_state_bytes_moved_total'] / steps / 1e9:.3f}"
        f" GB a step (read and written); held experts with a token "
        f"{win['moe_experts_touched_total'] / layer_steps:.2f} of "
        f"{cfg.experts_held} (of {cfg.n_routed_experts} routed over), "
        f"fullest {win['moe_max_load_total'] / layer_steps:.2f} tokens, per "
        f"expert layer and step; assignments computed here "
        f"{win['moe_assignments_total']}, left to other shares "
        f"{win['moe_assignments_elsewhere_total']}; cached tokens attended "
        f"a step {win['kv_ctx_tokens_total'] / steps:.0f}; slots seated a "
        f"decoding row {win['state_slots_held_total'] / rows:.3f}; slots "
        f"seated (and taken as zero) in the window "
        f"{win['state_slots_zeroed_total']}; prefill chunks x layers "
        f"{win['prefill_ssm_chunks_total']} "
        f"({win['prefill_ssm_positions_total']} positions); now: pages "
        f"{srv.pages_in_use_by_class}, slots {srv.state_slots_in_use}")
    _longest_phases(say)


def run(ctx: Dict) -> Dict:
    from bigdl_tpu.llm.models.nemotron_h import NemotronHForCausalLM
    return serve_mimo.run_window(
        ctx, model_config=model_config, seeded_params=seeded_params,
        model_class=NemotronHForCausalLM, serve_for_check=serve_for_check,
        compare_served=compare_served, report_family=report_family)
