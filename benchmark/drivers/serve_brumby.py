"""The serving driver for configurations of the ``brumby`` family (power
retention: no layer keeps a token, every layer holds a state of fixed
size a request): one ``LLMServer`` on one chip under the cell's
open-loop traffic, measured from the caller's side. The window loop is
``serve_mimo.run_window`` with what this family brings.

``correct`` is ``serve.py``'s (no failed or wrong request, no pass
error, no program first used inside the window, the kernel programs
hold their Mosaic calls) and, after the window, at the run's widths, a
chain from what the ENGINE served to the float32 reference. One prompt
of several prefill chunks is served while a dozen other rows are live,
seated in a slot another request has left; :class:`Tap` keeps the
logits row the engine computed at its prefill and at each of its decode
steps, the state the engine holds for it after its last step, and the
answer of the served decode dispatch to one probe query over that
state. That is the check's served half (:func:`serve_for_check`); a run
then lets the engine and its state go and computes the comparisons
(:func:`compare_served`) beside the weights alone, so that
``memory_peak_bytes`` is what the served state held.

- (a) logits: every served token within the configuration's tolerance
  of the float32 reference's maximum (``reference.margins``) and the
  engine's rows within a limit of the reference's in the median
  (``row_distance``). The reference computes the attention form over
  the same ids: no state, no chunk, no ``phi``.
- (b) state: what the engine holds for the request after its last step,
  every layer, against ``sum_s exp(sum g) k_s k_s^T (x) v_s`` and the
  normaliser built directly from the REFERENCE's own keys, values and
  gates (``reference_brumby.state_of``): root-mean-square difference in
  units of the reference's root mean square, the largest of the layers.
  The packed symmetric state is unpacked to the full square first, so
  the layout, the ``sqrt 2`` and the padding are all in the comparison.
- (c) probe: one random query, key, value and gate through the served
  decode dispatch (``kernels.retention.retention_decode``, the kernel,
  the live-row walk, the in-place update) over a copy of the request's
  layer-0 state, against the float64 attention form over the
  reference's layer-0 keys, values and gates and the probe's token.
- (d) the engine's own books: the served tokens are the argmax of the
  rows it computed; at least ``company`` other rows were live at each
  of its steps; its slot had held another request before (the ledger's
  count); and ``state_rows_total`` and ``state_slots_held_total`` moved
  by what the tap saw dispatched.
- (a) once more, for what a long prompt cannot show: under gates of
  0.92 to 0.9975 nothing from before a 4,100-token prompt is left in
  the state after it, so a slot that was NOT taken as zero reads as
  clean there. Right after the long request a **short** one (256
  tokens, 16 served) is seated where one of the company's states lies,
  and its logits rows are held to the reference's by the same limit:
  a quarter of the leftover state is still there after 256 positions.

``benchmark/check_brumby.py`` measures the floors these limits come
from and plants faults in the served program to see each one fail.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import reference, reference_brumby, weights_brumby
from benchmark.drivers import serve_mimo
from benchmark.drivers.serve_deepseek import row_distance


def model_config(config: Dict, override: Dict):
    from bigdl_tpu.llm.models.brumby import BrumbyConfig
    return BrumbyConfig.from_hf_config({**config, **override})


def seeded_params(cfg, seed: int, config: Dict):
    return weights_brumby.seeded_bf16_params(
        cfg, seed, float(config["weights_back_gain"]),
        float(config["weights_gate_spread"]), config["weights_gate_bias"])


class Tap:
    """What the engine computed for ONE request (the one whose prompt is
    ``prompt``), as device arrays, taken on the engine thread where the
    engine holds them. ``rows``: the last-position logits row of its
    prefill, then its row of every decode step (row ``k`` is what served
    token ``k`` was sampled from). ``live``: rows each of those steps
    advanced. ``slot`` and ``seatings``: where it was seated and how
    often that slot had been seated by then. Right after its last step:
    ``state`` and ``z``, its row of the state class's two arrays, every
    layer, and ``probe``, the served decode dispatch on one random
    token over a copy of layer 0's. ``seen`` sums, over every decode
    dispatch while the tap is on, the rows dispatched and the slots
    seated: the host's own count for the engine's counters."""

    def __init__(self, srv, cfg, prompt: np.ndarray, seed: int,
                 keep_state: bool = True):
        import jax
        import jax.numpy as jnp
        self.rows: List = []
        self.live: List[int] = []
        self.slot, self.seatings = -1, 0
        self.state = self.z = self.probe = None
        self.state_len = 0
        self.seen = {"state_rows_total": 0, "state_slots_held_total": 0,
                     "decode_rows_total": 0, "steps": 0}
        self._srv = srv
        finish, after = srv._finish_prefill, srv._after_dispatch
        (ledger,) = srv._states
        rs = np.random.RandomState(seed % (2 ** 31))
        hkv, grp, d = cfg.num_key_value_heads, cfg.group, cfg.head_dim
        # the probe's token: a unit-norm-a-number query and key (as the
        # model's are after their norms), a value, a gate of 0.97
        self.q = rs.randn(hkv, grp, d).astype(np.float32)
        self.k = rs.randn(hkv, d).astype(np.float32)
        self.v = rs.randn(hkv, d).astype(np.float32)
        self.g = np.full((hkv,), np.log(0.97), np.float32)

        def mine(req) -> bool:
            p = req.prompt_ids
            return p.shape == prompt.shape and bool((p == prompt).all())

        def finish_prefill(i, req, row_pages, own, last, *a, **k):
            if mine(req):
                self.rows.append(last)
                self.slot, self.seatings = i, ledger.seatings[i]
            return finish(i, req, row_pages, own, last, *a, **k)

        @jax.jit
        def probed(state, z, q, k, v, g):
            from bigdl_tpu.llm.kernels import retention
            # rows: [trash, the request's]; batch: [a dead row, the live]
            two = lambda a: jnp.stack([jnp.zeros_like(a), a])
            y, s, zz = retention.retention_decode(
                two(state), two(z), two(q), two(k), two(v), two(g),
                jnp.asarray([0, 1], jnp.int32), jnp.asarray([False, True]),
                eps=cfg.retention_eps)
            return y[1], s[1], zz[1]

        def after_dispatch(rec, t0):
            if rec.get("fn") == "llm/decode_paged":
                self.seen["state_rows_total"] += len(rec["pairs"])
                self.seen["decode_rows_total"] += len(rec["pairs"])
                self.seen["state_slots_held_total"] += \
                    ledger.slots_in_use()
                self.seen["steps"] += 1
                for i, req in rec["pairs"]:
                    if mine(req):
                        self.rows.append(srv._last[i])
                        self.live.append(len(rec["pairs"]))
                        if keep_state and srv._remaining[i] == 0:
                            # right after its last step
                            self.state_len = int(srv._lens[i])
                            row = int(ledger.rows[i, 0])
                            self.state = srv._k_pages[:, row]
                            self.z = srv._v_pages[:, row]
                            self.probe = probed(
                                self.state[0], self.z[0],
                                *(jnp.asarray(a) for a in
                                  (self.q, self.k, self.v, self.g)))
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


def unpack_state(packed: np.ndarray, n: int) -> np.ndarray:
    """The symmetric state as the kernels hold it, ``(..., P)`` with the
    products ``c_d x_a x_((a + d) mod n)`` at ``d n + a``, to the full
    square ``(..., n, n)``: entry ``(a, b)`` and ``(b, a)`` the product
    ``x_a x_b`` itself."""
    packed = np.asarray(packed, np.float64)
    out = np.zeros(packed.shape[:-1] + (n, n))
    a = np.arange(n)
    for d in range(n // 2 + 1):
        tile = packed[..., d * n:(d + 1) * n] / (1.0 if d == 0
                                                 else np.sqrt(2.0))
        keep = a if 2 * d != n else a[:n // 2]
        b = (keep + d) % n
        out[..., keep, b] = tile[..., keep]
        out[..., b, keep] = tile[..., keep]
    return out


def rel_rms(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).mean()
                         / max((want ** 2).mean(), 1e-300)))


def probe_distance(tap: Tap, cfg, rows0, upto: int) -> float:
    """The probe's answer against the float64 attention form of the
    probe's query over the reference's layer-0 keys, values and gates up
    to ``upto`` and the probe's own token: root-mean-square difference
    in units of the answer's root mean square."""
    if tap.probe is None:
        return float("inf")
    k, v, g = (np.asarray(a, np.float64)[:upto] for a in rows0)
    d = cfg.head_dim
    run = np.cumsum(g, axis=0)
    decay = np.exp(run[-1][None] - run + tap.g[None].astype(np.float64))
    q = tap.q.astype(np.float64)                            # (hkv, grp, d)
    a = np.einsum("hgd,thd->thg", q, k) ** 2 / d * decay[..., None]
    a_new = np.einsum("hgd,hd->hg", q, tap.k.astype(np.float64)) ** 2 / d
    num = np.einsum("thg,thv->hgv", a, v) \
        + a_new[..., None] * tap.v.astype(np.float64)[:, None, :]
    want = num / (a.sum(0) + a_new + cfg.retention_eps)[..., None]
    return rel_rms(np.asarray(tap.probe[0]), want)


def serve_tapped(srv, cfg, prompt: np.ndarray, new: int, company: int,
                 rs, seed: int):
    """``prompt`` served for ``new`` tokens while ``company`` other
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
    # every slot has held a request by now, whatever the window did:
    # the prompt is seated where another's state lies
    for f in [srv.submit(rs.randint(0, vocab, 256).astype(np.int32),
                         max_new_tokens=2) for _ in range(srv.max_batch)]:
        f.get(timeout=600)
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


def serve_short(srv, cfg, sizes: Dict, rs, seed: int) -> Dict:
    """One short request served alone, right after the long one: it is
    seated in the first free slot, where one of the company's states
    lies. Returns its prompt, tokens, logits rows, slot and that slot's
    seating number."""
    prompt = rs.randint(0, cfg.vocab_size, int(
        sizes.get("short_prompt_tokens", 256))).astype(np.int32)
    new = int(sizes.get("short_served_tokens", 16))
    tap = Tap(srv, cfg, prompt, seed + 1, keep_state=False)
    try:
        served = srv.submit(prompt, max_new_tokens=new).get(timeout=600)
        deadline = time.perf_counter() + 60
        while not srv.engine_idle() and time.perf_counter() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
    finally:
        rows = tap.close()
    return {"prompt": prompt, "served": served, "rows": rows[:new],
            "slot": tap.slot, "seatings": tap.seatings}


LIMITS = ("reference_tolerance_sigma", "reference_distance_median_max",
          "state_distance_max", "normaliser_distance_max",
          "probe_distance_max")


def judge(r: Dict, config: Dict) -> Dict[str, bool]:
    """The four verdicts from the readings ``compare_served`` took and
    the configuration's limits, and from nothing else (so that
    ``check_brumby.py --rejudge`` can hold kept readings to limits
    chosen after them)."""
    lim = {k: float(config[k]) for k in LIMITS}
    return {
        "d": bool(r["rows_taken"] and r["tokens_are_argmax_of_rows"]
                  and r["rows_live_min"] > r["company"]
                  and r["slot_seatings"] >= 2
                  and r["short_slot_seatings"] >= 2
                  and r["counters_agree"]),
        "a": bool(r["reference_finite"]
                  and r["margin_sigma_max"]
                  <= lim["reference_tolerance_sigma"]
                  and r["reference_distance_median"]
                  <= lim["reference_distance_median_max"]
                  and r["short_reference_distance_median"]
                  <= lim["reference_distance_median_max"]),
        "b": bool(r["state_distance_max"] <= lim["state_distance_max"]
                  and r["normaliser_distance_max"]
                  <= lim["normaliser_distance_max"]),
        "c": bool(r["probe_distance"] <= lim["probe_distance_max"])}


def serve_for_check(srv, cfg, seed: int, sizes: Dict) -> Dict:
    """The served half of the check: the prompt served beside its
    company with the tap on, and everything the comparison wants of the
    engine taken to the host, so that the engine and its state can go
    before the float32 reference is computed beside the weights."""
    t0 = time.perf_counter()
    n, new = sizes["prompt_tokens"], sizes["served_tokens"]
    rs = np.random.RandomState(seed % (2 ** 31))
    prompt = rs.randint(0, cfg.vocab_size, n).astype(np.int32)
    served, rows, tap, before = serve_tapped(
        srv, cfg, prompt, new, int(sizes["company"]), rs, seed)
    counted = {k: srv.step_counters[k] - before[k] for k in tap.seen
               if k != "steps"}
    took = {"t0": t0, "prompt": prompt, "new": new, "served": served,
            "rows": rows, "tap": tap, "counted": counted,
            "company": int(sizes["company"]),
            "state": None if tap.state is None
            else np.asarray(tap.state, np.float32),
            "z": None if tap.z is None else np.asarray(tap.z, np.float32),
            "short": serve_short(srv, cfg, sizes, rs, seed)}
    if tap.probe is not None:
        tap.probe = tuple(np.asarray(a, np.float32) for a in tap.probe)
    tap.state = tap.z = None
    return took


def compare_served(cfg, params, took: Dict, config: Dict, say) -> Dict:
    """The comparing half: what :func:`serve_for_check` took against the
    float32 reference."""
    t0, prompt, new, served, rows, tap, counted = (
        took[k] for k in ("t0", "prompt", "new", "served", "rows", "tap",
                          "counted"))
    n, d = len(prompt), cfg.head_dim
    # the last served token was fed (and folded into the state) but
    # nothing drawn after it: the state is compared over all of ids,
    # the logits stop before it
    ids = np.concatenate([prompt, np.asarray(served, np.int32)])
    ref_rows: List = []
    ref = reference_brumby.brumby_logits(cfg, params, ids, rows=ref_rows,
                                         last=new + 1)[:new]
    taken = len(served) == new and rows.shape == ref.shape \
        and took["state"] is not None and tap.state_len == len(ids)
    nothing = np.full(new, np.inf)
    ref_dist = row_distance(rows, ref) if taken else nothing
    m = reference.margins(ref, served) if len(served) == new else nothing
    s_dist, z_dist, probe = [np.inf], [np.inf], np.inf
    if taken:
        s_dist, z_dist = [], []
        for l, (k, v, g) in enumerate(ref_rows):
            want_s, want_z = reference_brumby.state_of(k, v, g)
            # held (hkv, dv, P): the square's axes first, the values last
            got_s = unpack_state(took["state"][l], d).transpose(0, 2, 3, 1)
            s_dist.append(rel_rms(got_s, want_s))
            z_dist.append(rel_rms(unpack_state(took["z"][l], d), want_z))
        probe = probe_distance(tap, cfg, ref_rows[0], len(ids))
    short = took["short"]
    ids2 = np.concatenate([short["prompt"],
                           np.asarray(short["served"][:-1], np.int32)])
    new2 = len(short["served"])
    ref2 = reference_brumby.brumby_logits(cfg, params, ids2, last=new2)
    short_dist = row_distance(short["rows"], ref2) \
        if short["rows"].shape == ref2.shape else np.full(1, np.inf)
    r = {"rows_taken": bool(taken),
         "tokens_are_argmax_of_rows": bool(
             taken and (rows.argmax(-1) == np.asarray(served)).all()),
         "rows_live_min": min(tap.live, default=0),
         "rows_live_max": max(tap.live, default=0),
         "company": took["company"],
         "slot": tap.slot, "slot_seatings": tap.seatings,
         "counters_agree": bool(tap.seen["steps"] > 0 and all(
             counted[k] == tap.seen[k] for k in counted)),
         "counted": counted, "dispatched": dict(tap.seen),
         "reference_finite": bool(np.all(np.isfinite(ref))),
         "margin_sigma_max": float(np.max(m)),
         "margin_sigma_mean": float(np.mean(m)),
         "reference_distance_median": float(np.median(ref_dist)),
         "reference_distance_max": float(np.max(ref_dist)),
         "short_reference_distance_median": float(np.median(short_dist)),
         "short_slot": short["slot"],
         "short_slot_seatings": short["seatings"],
         "state_distance_max": float(np.max(s_dist)),
         "state_distance_by_layer": [round(x, 5) for x in s_dist],
         "normaliser_distance_max": float(np.max(z_dist)),
         "normaliser_distance_by_layer": [round(x, 5) for x in z_dist],
         "probe_distance": float(probe)}
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
        f"{counted} against {tap.seen} dispatched -> {word('d')}")
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
        f"from the reference's, the same limit -> {word('a')}")
    say(f"reference: (b) state: what the engine holds after "
        f"{tap.state_len} positions lies {r['state_distance_max']:.5f} "
        f"(the largest of the layers {r['state_distance_by_layer']}) of "
        f"its size from the sum built from the reference's keys, values "
        f"and gates, at most {config['state_distance_max']}; the "
        f"normaliser {r['normaliser_distance_max']:.5f} "
        f"({r['normaliser_distance_by_layer']}), at most "
        f"{config['normaliser_distance_max']} -> {word('b')}")
    say(f"reference: (c) probe: one random token through the served "
        f"decode dispatch over layer 0's state lies "
        f"{r['probe_distance']:.5f} from the float64 attention form over "
        f"the reference's keys, at most {config['probe_distance_max']} "
        f"-> {word('c')}; {time.perf_counter() - t0:.2f} s")
    return {**ok, "readings": r}


def report_family(say, win: Dict, cfg, srv) -> None:
    """The family's line of the window's report."""
    steps = max(1, win["state_layer_steps_total"]) / cfg.num_hidden_layers
    say(f"window: state rows a decode step "
        f"{win['state_rows_total'] / steps:.2f}; state moved "
        f"{win['state_bytes_moved_total'] / steps / 1e9:.3f} GB a step "
        f"(read and written); slots seated a decoding row "
        f"{win['state_slots_held_total'] / max(1, win['decode_rows_total']):.3f}"
        f"; slots seated (and taken as zero) in the window "
        f"{win['state_slots_zeroed_total']}; prefill chunks x layers "
        f"{win['prefill_state_chunks_total']} "
        f"({win['prefill_state_positions_total']} positions); slots "
        f"seated now "
        f"{srv.state_slots_in_use}")
    _longest_phases(say)


def _longest_phases(say, back_s: float = 120.0, top: int = 4) -> None:
    """The engine's longest phases of the last ``back_s`` seconds (the
    window and its drain), by the trace ring, each with when it began:
    a stall of seconds (one run in fifteen had one, PERF.md section 7)
    is named by the phase it sat in."""
    from benchmark import spans
    now = time.perf_counter()
    phases = sorted((r for r in spans.ring() or [] if r["name"] in (
        "llm/admit", "llm/grant", "llm/dispatch", "llm/fence_wait",
        "llm/drain") and r.get("t0") is not None
        and r["t0"] >= now - back_s), key=lambda r: -r["dur"])[:top]
    say(f"window: the engine's longest phases of the last {back_s:g} s: "
        + ", ".join(f"{r['name']} {r['dur'] / 1e3:.1f} ms at "
                    f"{r['t0'] - now:.1f} s" for r in phases))


def run(ctx: Dict) -> Dict:
    from bigdl_tpu.llm.models.brumby import BrumbyForCausalLM
    return serve_mimo.run_window(
        ctx, model_config=model_config, seeded_params=seeded_params,
        model_class=BrumbyForCausalLM, serve_for_check=serve_for_check,
        compare_served=compare_served, report_family=report_family)
