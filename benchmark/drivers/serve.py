"""The serving driver: one ``LLMServer`` on one chip under the cell's
traffic mix (an open loop), measured from the caller's side.

From the program it takes only what a user calls (``LlamaConfig``,
``synthetic_q4_params`` through ``benchmark/weights.py``,
``LlamaForCausalLM``, ``LLMServer.submit`` / ``Request``,
``compiled_steps``) and the engine's always-on counters
(``host_seconds``, ``stall_seconds``, ``steps``,
``prefill_tokens_total``, ``pass_errors``). Two things reach under that
surface, both guarded so a refactor cannot break a run: the page-grant
warm-up reads the shape of ``_bt_dev``, and a traced run wraps three
engine methods in ``TraceAnnotation`` spans so that idle gaps on the
device can be named by what the engine thread was doing.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import reference, stats, trace_reduce, traffic, weights

PAGE = 16  # LLMServer's default page size; no cell changes it


class Track:
    """One request as its caller sees it."""
    __slots__ = ("req", "due", "sent", "max_new", "stamps", "seen",
                 "closed", "refused")

    def __init__(self, req, due: float, sent: float, max_new: int,
                 refused: Optional[str] = None):
        self.req, self.due, self.sent, self.max_new = req, due, sent, max_new
        self.stamps = np.zeros(max_new + 1)
        self.seen = 0
        self.closed = req is None
        self.refused = refused


class Observer(threading.Thread):
    """Polls ``len(req.tokens)`` of every live request about once a
    millisecond and stamps each new token with the host clock: the
    default configuration keeps no per-token stamps of its own
    (``bigdl.slo.enabled=false``). Also integrates how many requests are
    waiting for a first token and how many are decoding."""

    def __init__(self, period: float = 0.001):
        super().__init__(name="bench-observer", daemon=True)
        self.period = period
        self.tracks: List[Track] = []       # appended to by the senders
        self.halt = threading.Event()
        self.worst_period = 0.0
        self.window = (float("inf"), float("inf"))
        self.decoding_area = 0.0            # row-seconds inside the window
        self.samples: List[tuple] = []      # (t, waiting, decoding)

    def run(self):
        live: List[Track] = []
        taken = 0
        last = time.perf_counter()
        next_sample = last
        while not self.halt.is_set():
            now = time.perf_counter()
            dt, last = now - last, now
            if self.window[0] <= now < self.window[1]:
                self.worst_period = max(self.worst_period, dt)
            n_tracks = len(self.tracks)
            if n_tracks > taken:
                live.extend(self.tracks[taken:n_tracks])
                taken = n_tracks
            waiting = decoding = 0
            done_any = False
            for tr in live:
                n = len(tr.req.tokens)
                if n > tr.seen:
                    tr.stamps[tr.seen:n] = now
                    tr.seen = n
                if tr.req.done.is_set() and tr.seen >= len(tr.req.tokens):
                    tr.closed = done_any = True
                elif tr.seen:
                    decoding += 1
                else:
                    waiting += 1
            if done_any:
                live = [tr for tr in live if not tr.closed]
            if self.window[0] <= now < self.window[1]:
                self.decoding_area += decoding * dt
            if now >= next_sample:
                self.samples.append((now, waiting, decoding))
                next_sample = now + 0.25
            time.sleep(self.period)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left - 0.0005 if left > 0.002 else 0)


def _submit(srv, obs: Observer, r: Dict, due: float) -> Track:
    sent = time.perf_counter()
    try:
        req = srv.submit(r["prompt"], max_new_tokens=r["max_new"])
    except Exception as e:  # refused at submit: counts as failed
        return Track(None, due, sent, r["max_new"],
                     refused=f"{type(e).__name__}: {e}")
    tr = Track(req, due, sent, r["max_new"])
    obs.tracks.append(tr)
    return tr


def _llama_config(config: Dict, override: Dict):
    from bigdl_tpu.llm.models.llama import LlamaConfig
    keys = {**config, **override}
    names = {f.name for f in dataclasses.fields(LlamaConfig)}
    return LlamaConfig(**{k: v for k, v in keys.items() if k in names})


def _program_names(say) -> Dict[str, List[str]]:
    """HLO module name and Mosaic-call count of every program the engine
    compiled, by kind, read from the engine's own executables."""
    import re

    from bigdl_tpu.llm.serving import compiled_steps
    names: Dict[str, List[str]] = {}
    pallas: Dict[str, int] = {}
    for kind, _detail, fn in compiled_steps():
        for _, exe in fn.executables():
            text = exe.as_text()
            m = re.match(r"HloModule\s+([^\s,]+)", text)
            if m and m.group(1) not in names.setdefault(kind, []):
                names[kind].append(m.group(1))
            n = text.count("tpu_custom_call")
            pallas[kind] = min(pallas.get(kind, n), n)
    say(f"engine programs: {names}; tpu_custom_call sites (least per "
        f"kind): {pallas}")
    return {"names": names, "pallas": pallas}


def _annotate_engine(srv) -> None:
    """Traced runs only: host spans around the engine thread's phases."""
    import jax
    for name in ("_admit", "_step_paged", "_drain_next"):
        fn = getattr(srv, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _label="engine/" + name.lstrip("_"), **k):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **k)
        setattr(srv, name, wrapped)


def _warm(srv, mix: Dict, vocab: int, scale: float, max_batch: int, say):
    """Every shape the window will use, and no other: one request per
    prefill bucket of the mix (all at once, so decode runs with several
    rows), then the block-table scatter for 1..max_batch granted rows."""
    import jax.numpy as jnp
    t0 = time.perf_counter()
    top = int(traffic.quantile_grid(mix["prompt"], 256, scale).max())
    buckets = traffic.prefill_buckets(mix, PAGE, scale)
    rs = np.random.RandomState(1)
    reqs = [srv.submit(rs.randint(0, vocab, min(b, top)).astype(np.int32),
                       max_new_tokens=4) for b in buckets]
    for r in reqs:
        r.get(timeout=1100)
    t1 = time.perf_counter()
    bt = getattr(srv, "_bt_dev", None)
    if bt is not None:
        z = jnp.zeros_like(bt)
        for n in range(1, min(max_batch, bt.shape[0]) + 1):
            idx = np.arange(n, dtype=np.int32)
            z = z.at[idx, idx].set(jnp.asarray(idx))
        z.block_until_ready()
    say(f"warm: prefill buckets {buckets} + 4 tokens each in "
        f"{t1 - t0:.2f} s; page-grant scatter for 1..{max_batch} rows in "
        f"{time.perf_counter() - t1:.2f} s")


def _reference_check(srv, cfg, params, seed: int, tol: float, say) -> bool:
    """One 64-token prompt served for 16 tokens, teacher-forced through
    the plain float32 reference; every served token must lie within
    ``tol`` row-sigmas of the reference's maximum."""
    t0 = time.perf_counter()
    rs = np.random.RandomState(seed % (2 ** 31))
    prompt = rs.randint(0, cfg.vocab_size, 64).astype(np.int32)
    served = srv.submit(prompt, max_new_tokens=16).get(timeout=600)
    ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    logits = reference.llama_logits(cfg, params, ids)[63:79]
    m = reference.margins(logits, served)
    ok = len(served) == 16 and bool(np.all(np.isfinite(logits))) \
        and float(m.max()) <= tol
    say(f"reference: 16 served tokens after a 64-token prompt lie at most "
        f"{m.max():.4f} (mean {m.mean():.4f}) logit-sigmas below the "
        f"float32 reference's maximum, worst at position "
        f"{64 + int(m.argmax())}; {int((m == 0).sum())}/16 are its argmax; "
        f"tolerance {tol}; {time.perf_counter() - t0:.2f} s -> "
        f"{'ok' if ok else 'FAILED'}")
    return ok


def _counters(srv) -> Dict[str, float]:
    return {"host_seconds": srv.host_seconds,
            "stall_seconds": srv.stall_seconds, "passes": srv.steps,
            "prefill_tokens": srv.prefill_tokens_total,
            "pass_errors": srv.pass_errors, "t": time.perf_counter()}


def _delta(a: Dict, b: Dict) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in a}


def run(ctx: Dict) -> Dict:
    import jax

    from bigdl_tpu.llm.models.llama import LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    say, config, mix = ctx["say"], ctx["config"], ctx["mix"]
    reh = config.get("rehearse", {}) if ctx["rehearse"] else {}
    cfg = _llama_config(config, reh.get("model", {}))
    engine = {**config["engine"], **reh.get("engine", {})}
    scale = float(reh.get("length_scale", 1.0))
    seconds = ctx["seconds"]

    t0 = time.perf_counter()
    with jax.default_device(ctx["devices"][0]):
        params = jax.block_until_ready(weights.conditioned_q4_params(
            cfg, ctx["seed"] % (2 ** 31 - 1)))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(params))
    say(f"weights: {nbytes / 2**30:.2f} GiB of conditioned sym_int4 params "
        f"on the device in {time.perf_counter() - t0:.2f} s")
    model = LlamaForCausalLM(cfg, params, max_cache_len=128)
    srv = LLMServer(model, **engine).start()
    say(f"server: LLMServer({engine}) started")
    obs = Observer()
    try:
        _warm(srv, mix, cfg.vocab_size, scale, engine["max_batch"], say)
        if ctx["trace"]:
            _annotate_engine(srv)
        reqs = traffic.requests(mix, ctx["seed"], seconds, cfg.vocab_size,
                                scale)
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
        at_open = _counters(srv)
        setup_s = t_open - ctx["t_start"]
        say(f"window opens {setup_s:.2f} s after process start")

        traced = None
        if ctx["trace"]:
            tconf = config.get("trace", {})
            start = min(tconf.get("start_s", 3.0), seconds / 4)
            length = min(tconf.get("slice_s", 4.0), seconds / 2)
            _sleep_until(t_open + start)
            ta = time.perf_counter()
            with trace_reduce.record() as tdir:
                slice_a = _counters(srv)
                time.sleep(length)
                slice_b = _counters(srv)
            say(f"trace: {length:g} s slice from {start:g} s into the "
                f"window; start_trace took {slice_a['t'] - ta:.2f} s, "
                f"stop_trace {time.perf_counter() - slice_b['t']:.2f} s")
            traced = (tdir, _delta(slice_a, slice_b))

        _sleep_until(t_close)
        at_close = _counters(srv)
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
        inside = [s for s in obs.samples if t_open <= s[0] < t_close]
        q = max(1, len(inside) // 4)
        quarters = [inside[i:i + q] for i in range(0, 4 * q, q)]
        backlog = [float(np.mean([w for _, w, _ in part])) if part else 0.0
                   for part in quarters]
        rows_mean = obs.decoding_area / seconds
        say(f"window: waiting for a first token, mean per quarter "
            f"{[round(b, 2) for b in backlog]}; rows decoding mean "
            f"{rows_mean:.2f} of {engine['max_batch']}")

        ok_ref = _reference_check(
            srv, cfg, params, ctx["seed"],
            float(config["reference_tolerance_sigma"]), say)
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
