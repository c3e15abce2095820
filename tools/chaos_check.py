"""Chaos harness (ISSUE 2 satellite): train the LeNet example under a
randomized-but-seeded fault-injection plan and assert the final loss
matches an uninjected run.

The determinism argument: the data pipeline is unshuffled, recovery
replays from the last epoch-boundary checkpoint with the exact batch
order, delays change no math, and corrupt checkpoint writes are
quarantined at restore time — so every injected schedule must converge
to the SAME final loss as the clean run. Any divergence means a failure
path dropped or replayed work incorrectly, which is precisely what this
harness exists to catch.

Usage:
    python tools/chaos_check.py [--seed N] [--events K] [--full]
        [--kvcache | --kvtier | --failover | --flight | --fleet
         | --preempt | --all]

Wired into ``bench.py``'s telemetry block as a smoke invocation and into
pytest as ``-m chaos`` (kept out of tier-1 by the ``slow`` marker).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import textwrap
from typing import Optional

import numpy as np

# runnable as `python tools/chaos_check.py` from the repo root: the
# script dir is on sys.path then, the package root is not
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _train_once(n: int, epochs: int, batch: int, ckpt_dir: Optional[str],
                max_retry: int = 0) -> float:
    """One deterministic LeNet training run (the examples/lenet_mnist
    model over synthetic digits, unshuffled) → final loss."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.feature.dataset import LocalDataSet
    from bigdl_tpu.models.lenet import build_model
    from bigdl_tpu.nn.module import set_seed
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    set_seed(0)
    rs = np.random.RandomState(0)
    x = rs.rand(n, 1, 28, 28).astype(np.float32)
    y = (rs.randint(0, 10, n) + 1).astype(np.int32)
    model = build_model(10)
    opt = LocalOptimizer(model, LocalDataSet(x, y, shuffle=False),
                         nn.ClassNLLCriterion(), batch_size=batch,
                         end_trigger=Trigger.max_epoch(epochs))
    if ckpt_dir:
        opt.set_checkpoint(ckpt_dir, Trigger.every_epoch())
    if max_retry:
        opt.set_max_retry(max_retry)
    opt.optimize()
    return float(opt.state["loss"])


def run_chaos(seed: int = 0, events: int = 5, smoke: bool = True,
              rtol: float = 1e-4) -> dict:
    """The harness: clean run, then the same run under an armed seeded
    plan (kill/corrupt/delay events over the training+checkpoint sites),
    assert the final losses match. Returns the comparison record."""
    from bigdl_tpu import reliability as rel

    n, epochs, batch = (64, 3, 16) if smoke else (256, 5, 32)
    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    try:
        clean = _train_once(n, epochs, batch, ckpt_dir=None)

        # the injected run: faults target the recovery-relevant sites;
        # the retry budget outnumbers the raise events so training
        # always completes; seeded => exactly reproducible
        plan = rel.FaultPlan(seed=seed).randomize(
            events, sites=("optimizer.step", "checkpoint.write",
                           "checkpoint.write.manifest",
                           "checkpoint.commit", "optimizer.checkpoint"))
        with tempfile.TemporaryDirectory() as ckpt_dir:
            rel.set_plan(plan)
            try:
                injected = _train_once(n, epochs, batch,
                                       ckpt_dir=ckpt_dir,
                                       max_retry=events + 1)
            finally:
                rel.set_plan(None)
    finally:
        if not was_enabled:
            rel.disable()   # leave the process how we found it

    match = bool(np.isclose(clean, injected, rtol=rtol, atol=1e-6))
    out = {
        "seed": seed,
        "events_armed": events,
        "events_fired": [f"{s}:{a}" for s, a in plan.fired],
        "clean_loss": clean,
        "injected_loss": injected,
        "match": match,
    }
    if not match:
        raise AssertionError(
            f"chaos divergence: clean loss {clean} vs injected "
            f"{injected} (fired: {out['events_fired']})")
    return out


def run_kvcache_chaos(seed: int = 0, n_requests: int = 6,
                      raises: int = 2) -> dict:
    """ISSUE 5 satellite: serve a shared-prefix workload through the
    prefix cache with seeded ``kvcache.evict`` faults armed (delays on
    every eviction to widen race windows, plus a few raises — the site
    fires before any state mutates, so the engine loop retries cleanly)
    and assert greedy outputs are token-identical to the clean cache-on
    run. The pool is sized small so eviction genuinely happens."""
    import numpy as np

    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, 250, 12).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rs.randint(0, 250, 2 + j % 5)
                               .astype(np.int32)])
               for j in range(n_requests)]

    def serve_all():
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=7, kvcache=True).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
            return ([list(map(int, r.get(timeout=300))) for r in reqs],
                    srv._kv.evictions)
        finally:
            srv.stop()

    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    try:
        clean, clean_evicts = serve_all()
        plan = rel.FaultPlan(seed=seed)
        # rules match first-wins: the bounded raises go first (skipping
        # the first call), the unbounded delays mop up every other pass
        plan.add("kvcache.evict", "raise", times=raises, after=1)
        plan.add("kvcache.evict", "delay", times=None, delay=0.002)
        rel.set_plan(plan)
        try:
            injected, injected_evicts = serve_all()
        finally:
            rel.set_plan(None)
    finally:
        if not was_enabled:
            rel.disable()

    match = injected == clean
    out = {
        "seed": seed,
        "requests": n_requests,
        "clean_evictions": clean_evicts,
        "injected_evictions": injected_evicts,
        "events_fired": [f"{s}:{a}" for s, a in plan.fired],
        "match": match,
    }
    if not out["events_fired"]:
        raise AssertionError(
            "kvcache chaos armed but no kvcache.evict fault fired — "
            "the pool was not under pressure; shrink it")
    if not match:
        raise AssertionError(
            f"kvcache chaos divergence under eviction faults "
            f"(fired: {out['events_fired']}): {clean} vs {injected}")
    return out


def run_kvtier_chaos(seed: int = 0, n_groups: int = 4,
                     fetch_raises: int = 2, spill_raises: int = 1) -> dict:
    """ISSUE 6 satellite: drive spill→reload traffic through the host
    tier with seeded ``kvtier.spill``/``kvtier.fetch`` faults armed —
    delays on every migration to widen the async windows, plus raises
    on both directions — and assert greedy outputs are token-identical
    to the clean tier-on run. The contract under failure: a failed
    spill is a plain eviction, a failed fetch a plain cache miss —
    never a stall, a crash, or a different token."""
    import numpy as np

    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    rs = np.random.RandomState(seed)
    groups = [rs.randint(0, 250, 16).astype(np.int32)
              for _ in range(n_groups)]
    prompts = []
    for rnd in range(2):          # two passes: seed chains, then reload
        for g in range(n_groups):
            prompts.append(np.concatenate(
                [groups[g], rs.randint(0, 250, 2 + (rnd + g) % 3)
                 .astype(np.int32)]))

    def serve_all():
        # pool fits ~2 of the 4 chains -> pass 2 must hit the arena
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=9, kvcache=True, kvtier=True,
                        host_pages=32).start()
        try:
            got = [list(map(int,
                            srv.submit(p, max_new_tokens=4)
                            .get(timeout=300)))
                   for p in prompts]
            return got, srv._tier.spills, srv._tier.fetches
        finally:
            srv.stop()

    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    try:
        clean, clean_spills, clean_fetches = serve_all()
        plan = rel.FaultPlan(seed=seed)
        # first-match-wins: bounded raises first, unbounded delays mop
        # up every other migration
        plan.add("kvtier.fetch", "raise", times=fetch_raises, after=0)
        plan.add("kvtier.spill", "raise", times=spill_raises, after=1)
        plan.add("kvtier.*", "delay", times=None, delay=0.003)
        rel.set_plan(plan)
        try:
            injected, inj_spills, inj_fetches = serve_all()
        finally:
            rel.set_plan(None)
    finally:
        if not was_enabled:
            rel.disable()

    match = injected == clean
    out = {
        "seed": seed,
        "requests": len(prompts),
        "clean_spills": clean_spills,
        "clean_fetches": clean_fetches,
        "injected_spills": inj_spills,
        "injected_fetches": inj_fetches,
        "events_fired": [f"{s}:{a}" for s, a in plan.fired],
        "match": match,
    }
    if clean_fetches == 0:
        raise AssertionError(
            "kvtier chaos: the clean run never fetched from the host "
            "arena — the pool is not under pressure; shrink it")
    if not any(s.startswith("kvtier.") for s, _ in plan.fired):
        raise AssertionError(
            "kvtier chaos armed but no kvtier fault fired")
    if not match:
        raise AssertionError(
            f"kvtier chaos divergence under migration faults "
            f"(fired: {out['events_fired']}): {clean} vs {injected}")
    return out


def run_mixed_chaos(seed: int = 0, raises: int = 2) -> dict:
    """ISSUE 14 satellite: drive chunked admissions through the unified
    mixed engine with seeded ``llm.chunk`` faults armed — delays on
    every chunk boundary to widen the interleaving windows, plus raises
    that kill an admission MID-CHAIN. The contract under failure: the
    partial chain's pages and ledger charges roll back completely (the
    idle budget equals the clean run's), the request fails RETRIABLY,
    and a resubmission produces greedy output token-identical to the
    clean run."""
    import numpy as np

    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, 250, 16).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rs.randint(0, 250, 16 + 8 * (j % 2))
                               .astype(np.int32)])
               for j in range(3)]                      # 32/40-token, chunked
    prompts.append(rs.randint(0, 250, 6).astype(np.int32))   # short

    num_pages = 32

    def serve_all(resubmit: bool):
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=num_pages, kvcache=True, mixed=True,
                        chunk_tokens=8).start()
        failed = 0
        try:
            reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
            outs = []
            for j, r in enumerate(reqs):
                try:
                    outs.append(list(map(int, r.get(timeout=300))))
                except RuntimeError as e:
                    if "retriable" not in str(e):
                        raise
                    failed += 1
                    if not resubmit:
                        raise
                    r2 = srv.submit(prompts[j], max_new_tokens=4)
                    outs.append(list(map(int, r2.get(timeout=300))))
        finally:
            srv.stop()
        # read AFTER stop: the drain resolved every deferred fence
        # release, so a nonzero delta is a real ledger leak
        return (outs, failed, srv.prefill_chunks_total,
                srv._budget_avail)

    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    try:
        clean, _, clean_chunks, clean_budget = serve_all(resubmit=False)
        plan = rel.FaultPlan(seed=seed)
        # first-match-wins: bounded raises kill admissions mid-chain,
        # the unbounded delays stretch every other chunk boundary
        plan.add("llm.chunk", "raise", times=raises, after=1)
        plan.add("llm.chunk", "delay", times=None, delay=0.002)
        rel.set_plan(plan)
        try:
            injected, failed, inj_chunks, inj_budget = \
                serve_all(resubmit=True)
        finally:
            rel.set_plan(None)
    finally:
        if not was_enabled:
            rel.disable()

    match = injected == clean
    out = {
        "seed": seed,
        "requests": len(prompts),
        "clean_chunks": clean_chunks,
        "injected_chunks": inj_chunks,
        "failed_retriably": failed,
        "clean_idle_budget": clean_budget,
        "injected_idle_budget": inj_budget,
        "events_fired": [f"{s}:{a}" for s, a in plan.fired],
        "match": match,
    }
    if clean_chunks == 0:
        raise AssertionError(
            "mixed chaos: the clean run never chunked — prompts are "
            "shorter than chunk_tokens; lengthen them")
    if not any(s == "llm.chunk" for s, _ in plan.fired):
        raise AssertionError(
            "mixed chaos armed but no llm.chunk fault fired")
    if failed == 0:
        raise AssertionError(
            "mixed chaos: no admission failed mid-chain — the raise "
            "rule never landed between chunks")
    if inj_budget != clean_budget or inj_budget != num_pages - 1:
        raise AssertionError(
            f"mixed chaos ledger leak: idle budget {inj_budget} vs "
            f"clean {clean_budget} (pool {num_pages - 1})")
    if not match:
        raise AssertionError(
            f"mixed chaos divergence under chunk faults "
            f"(fired: {out['events_fired']}): {clean} vs {injected}")
    return out


def run_spec_chaos(seed: int = 0, raises: int = 2) -> dict:
    """ISSUE 19 satellite: self-speculative decoding under faults.

    A repetitive-suffix workload (so the n-gram proposer genuinely
    drafts) is served twice: spec OFF clean, then spec ON with seeded
    ``llm.spec`` faults armed — the site fires between drafting and
    the verify dispatch, so a raise must degrade that tick to a plain
    decode step, never a wrong token. The contract: greedy outputs
    BIT-IDENTICAL to the spec-off run, the page ledger idle after
    stop (speculative pages release with the slot), and the
    proposed/accepted counters reconciling EXACTLY with the flight
    ``draft``/``verify_accept``/``verify_reject`` events (same call
    sites — any drift is a forked emission path)."""
    import numpy as np

    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.observability import flight
    from bigdl_tpu.utils.conf import conf

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    # the long prompt's pattern is pinned to the seed whose greedy
    # CONTINUATION cycles (what prompt-lookup drafts from is generated
    # history, so acceptance needs the output to repeat) — the fault
    # plan still randomizes on ``seed``
    pattern = np.random.RandomState(42).randint(0, 250, 5) \
        .astype(np.int32)
    rs = np.random.RandomState(seed)
    prompts = [np.tile(pattern, 6).astype(np.int32),
               np.concatenate([pattern,
                               rs.randint(0, 250, 4).astype(np.int32)]),
               rs.randint(0, 250, 9).astype(np.int32)]
    new_tokens = [24, 8, 8]

    num_pages = 24

    def serve_all(sp: bool):
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=num_pages, spec=sp, spec_k=4).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, new_tokens)]
            outs = [list(map(int, r.get(timeout=300))) for r in reqs]
        finally:
            srv.stop()
        # read AFTER stop: the drain resolved every in-flight verify,
        # so a nonzero delta is a real page leak
        return (outs, srv._budget_avail,
                {"passes": srv.spec_passes,
                 "proposed": srv.spec_proposed_total,
                 "accepted": srv.spec_accepted_total,
                 "emitted": srv.spec_emitted_total})

    def _spec_events():
        r = flight.ring()
        evs = r.events() if r is not None else []
        return {
            "draft": sum(1 for e in evs if e["kind"] == "draft"),
            "drafted": sum(e.get("detail", {}).get("n_draft", 0)
                           for e in evs if e["kind"] == "draft"),
            "verdicts": sum(1 for e in evs
                            if e["kind"] in ("verify_accept",
                                             "verify_reject")),
            "accepted": sum(e.get("detail", {}).get("accepted", 0)
                            for e in evs
                            if e["kind"] in ("verify_accept",
                                             "verify_reject")),
            "dropped": r.dropped if r is not None else 0,
        }

    GATE = "bigdl.observability.flight.enabled"
    with conf._lock:
        prev = conf._set_layer.get(GATE)
    conf.set(GATE, "true")
    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    try:
        clean, clean_budget, _ = serve_all(sp=False)
        ev_before = _spec_events()
        c_before = {
            "proposed": _counter_total(
                "bigdl_llm_spec_proposed_tokens_total"),
            "accepted": _counter_total(
                "bigdl_llm_spec_accepted_tokens_total"),
        }
        plan = rel.FaultPlan(seed=seed)
        # first-match-wins: bounded raises kill a speculative tick
        # between the draft and its dispatch (degrade to plain decode),
        # the unbounded delays stretch every other one
        plan.add("llm.spec", "raise", times=raises, after=1)
        plan.add("llm.spec", "delay", times=None, delay=0.002)
        rel.set_plan(plan)
        try:
            injected, inj_budget, stats = serve_all(sp=True)
        finally:
            rel.set_plan(None)
        ev_delta = {k: _spec_events()[k] - ev_before[k]
                    for k in ev_before}
        c_after = {
            "proposed": _counter_total(
                "bigdl_llm_spec_proposed_tokens_total"),
            "accepted": _counter_total(
                "bigdl_llm_spec_accepted_tokens_total"),
        }
    finally:
        rel.set_plan(None)
        if not was_enabled:
            rel.disable()
        if prev is None:
            conf.unset(GATE)
        else:
            conf.set(GATE, prev)

    match = injected == clean
    out = {
        "seed": seed,
        "requests": len(prompts),
        "spec_passes": stats["passes"],
        "proposed": stats["proposed"],
        "accepted": stats["accepted"],
        "clean_idle_budget": clean_budget,
        "injected_idle_budget": inj_budget,
        "events_fired": [f"{s}:{a}" for s, a in plan.fired],
        "flight_events": ev_delta,
        "match": match,
    }
    if stats["passes"] == 0 or stats["accepted"] == 0:
        raise AssertionError(
            "spec chaos: the spec-on run never speculated (or never "
            "accepted a draft) — the workload's continuation is not "
            "repetitive enough, so the reconciliation is vacuous")
    if not any(s == "llm.spec" for s, _ in plan.fired):
        raise AssertionError(
            "spec chaos armed but no llm.spec fault fired")
    if inj_budget != clean_budget or inj_budget != num_pages - 1:
        raise AssertionError(
            f"spec chaos page leak: idle budget {inj_budget} vs clean "
            f"{clean_budget} (pool {num_pages - 1})")
    if not match:
        raise AssertionError(
            f"spec chaos divergence under llm.spec faults "
            f"(fired: {out['events_fired']}): {clean} vs {injected}")
    if ev_delta["dropped"]:
        raise AssertionError(
            "flight ring dropped events mid-check; raise "
            "bigdl.observability.flight.capacity")
    # the reconciliation: EXACT — the events are emitted at the same
    # call sites as the counter increments and the plain-int ledgers
    if ev_delta["draft"] != stats["passes"] \
            or ev_delta["verdicts"] != stats["passes"]:
        raise AssertionError(
            f"flight draft/verdict events ({ev_delta['draft']}/"
            f"{ev_delta['verdicts']}) != {stats['passes']} spec passes")
    if ev_delta["drafted"] != stats["proposed"] \
            or ev_delta["accepted"] != stats["accepted"]:
        raise AssertionError(
            f"flight drafted/accepted token tallies {ev_delta} != "
            f"engine ledgers {stats}")
    if c_before["proposed"] is not None:
        for key in ("proposed", "accepted"):
            got = c_after[key] - c_before[key]
            if got != stats[key]:
                raise AssertionError(
                    f"bigdl_llm_spec_{key}_tokens_total delta ({got}) "
                    f"!= engine ledger ({stats[key]})")
        out["counters_reconciled"] = True
    else:
        out["counters_reconciled"] = "obs disabled: ledger-only"
    return out


def run_failover_chaos(seed: int = 0, n_requests: int = 4,
                       kills: int = 2, stalls: int = 1,
                       new_tokens: int = 5,
                       smoke: bool = False) -> dict:
    """ISSUE 7 acceptance: a kill storm against the disaggregated
    router must cost latency, not answers. Two decode workers behind a
    failover-enabled ``LLMRouter``; seeded ``router.dispatch`` raises
    tear connections mid-stream (after tokens drained) and seeded
    ``worker.stall`` hangs wedge an engine past its watchdog timeout —
    every request must still complete with greedy output bit-identical
    to ``model.generate``, with the journal resuming
    ``prompt + generated_so_far`` on the surviving backend.

    Also asserts the disabled-mode contract: with failover/hedging off
    the router is structurally the PR 6 object — no journal, no prober
    thread, no ``bigdl_router_failovers/hedges/journal`` metric series
    from serving a request through it.

    ``smoke=True`` shrinks the storm to one kill over two requests
    (dominant costs are the per-shape warmup on both engines and the
    watchdog stall) — the same contract, sized for ``run_all_chaos``
    inside ``bench.py`` telemetry where the full storm's minutes of
    wall-clock would distort a tool people compare numbers across."""
    import threading

    if smoke:
        n_requests = min(n_requests, 2)
        kills = min(kills, 1)
        new_tokens = min(new_tokens, 4)

    import numpy as np

    from bigdl_tpu import observability as obs
    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.llm.worker import LLMRouter, LLMWorker

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(0, 250, 10 + 2 * j).astype(np.int32)
               for j in range(n_requests)]
    want = [list(map(int,
                     model.generate(p[None],
                                    max_new_tokens=new_tokens)
                     [0, len(p):]))
            for p in prompts]

    def post(addr, path, body, timeout=600):
        import http.client
        import json as _json
        conn = http.client.HTTPConnection(*addr, timeout=timeout)
        try:
            conn.request("POST", path, _json.dumps(body),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, _json.loads(r.read().decode())
        finally:
            conn.close()

    # --- disabled-mode structural absence (cheap, serves one request)
    s0 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8) \
        .start()
    w0 = LLMWorker(s0, role="decode").start()
    before = set(obs.render().splitlines()) if obs.enabled() else set()
    r0 = LLMRouter([], [w0.address], start_prober=False).start()
    try:
        assert r0._journal is None and r0._prober is None \
            and r0._hedge is None, "disabled router built failover state"
        assert not s0.watchdog_enabled and s0._watchdog_thread is None
        st, body = post(r0.address, "/worker_generate",
                        {"prompt_ids": [int(t) for t in prompts[0]],
                         "max_new_tokens": 2})
        assert st == 200, body
        if obs.enabled():
            new = "\n".join(set(obs.render().splitlines()) - before)
            for name in ("bigdl_router_failovers_total",
                         "bigdl_router_hedges_total",
                         "bigdl_router_journal_inflight",
                         "bigdl_router_backend_healthy",
                         # ISSUE 12: SLO sketches and classification
                         # series must be structurally absent too
                         "bigdl_llm_ttft_seconds",
                         "bigdl_llm_itl_seconds",
                         "bigdl_router_ttft_seconds",
                         "bigdl_router_itl_seconds",
                         "bigdl_slo_requests_total",
                         "bigdl_slo_burn_rate"):
                assert name not in new, \
                    f"disabled mode grew metric series {name}"
        assert s0._slo is None and r0._slo is None, \
            "disabled mode built an SLO account"
        assert r0._collector is None, \
            "disabled mode built a federation collector"
        assert not [t for t in threading.enumerate()
                    if t.name in ("bigdl-router-prober",
                                  "bigdl-federation-collector")], \
            "disabled mode started a prober/collector thread"
    finally:
        r0.stop()
        w0.stop()
        s0.stop()

    # --- the storm: kills mid-stream + a watchdog-tripping stall
    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    # watchdog above the warmed per-step time but under the stall; the
    # engines are warmed below so compiles don't masquerade as stalls
    # SLO accounting rides the storm (ISSUE 12): the counters and the
    # router's token-arrival sketches must survive mid-stream failover
    # with resumed tokens counted exactly once
    s1 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                   kvcache=True, watchdog_timeout=0.6, slo=True).start()
    s2 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                   kvcache=True, watchdog_timeout=0.6, slo=True).start()
    w1 = LLMWorker(s1, role="decode").start()
    w2 = LLMWorker(s2, role="decode").start()
    router = LLMRouter([], [w1.address, w2.address], failover=True,
                       failover_attempts=8, start_prober=False,
                       slo=True).start()
    # sketch/counter state BEFORE the storm: the registry is process-
    # global (bench's chaos_all runs several suites), so every SLO
    # assertion below is on the delta
    def _slo_counts():
        if not obs.enabled():
            return None
        reg = obs.REGISTRY
        classified = sum(
            reg.sample_value("bigdl_slo_requests_total", slo="ttft",
                             verdict=v, scope="router") or 0.0
            for v in ("ok", "violated"))
        return {
            "ttft": reg.sample_value("bigdl_router_ttft_seconds") or 0.0,
            "itl": reg.sample_value("bigdl_router_itl_seconds") or 0.0,
            "classified": classified}
    slo_before = _slo_counts()
    try:
        # warm EVERY shape the storm will hit on both engines: the
        # first submit compiles the full prefill + decode steps, the
        # second hits the radix index it just seeded and compiles the
        # partial-prefill suffix shape — the same shape every
        # journal resume (prompt + generated, suffix re-prefill) uses.
        # An unwarmed compile stalls the heartbeat exactly like a hung
        # step and would trip the watchdog on the compile instead of
        # the injected stall (see LLMServer._watchdog_loop).
        for srv in (s1, s2):
            for p in prompts:
                srv.submit(p, max_new_tokens=1).get(timeout=600)
                srv.submit(p, max_new_tokens=1).get(timeout=600)
        plan = rel.FaultPlan(seed=seed)
        # mid-stream connection kills: each bounded raise tears the
        # router->worker stream a few drained chunks in (llm.step is
        # slowed so chunks arrive one token at a time, and the
        # dispatch site fires once per drained chunk)
        for k in range(kills):
            plan.add("router.dispatch", "raise", times=1, after=3 + 2 * k)
        # a wedged device step, longer than the 0.6 s watchdog: the
        # victim engine trips mid-generation (the site only fires with
        # live slots), fails its requests retriably, recovers
        plan.add("worker.stall", "delay", times=stalls, after=2,
                 delay=1.5)
        plan.add("llm.step", "delay", times=None, delay=0.02)
        rel.set_plan(plan)
        got = []
        failures = []
        try:
            for j, p in enumerate(prompts):
                st, body = post(router.address, "/worker_generate",
                                {"prompt_ids": [int(t) for t in p],
                                 "max_new_tokens": new_tokens})
                if st != 200:
                    failures.append((j, st, body.get("error")))
                    got.append(None)
                else:
                    got.append(body["output_ids"])
        finally:
            rel.set_plan(None)
            if not was_enabled:
                rel.disable()
        out = {
            "seed": seed,
            "requests": n_requests,
            "events_fired": [f"{s}:{a}" for s, a in plan.fired],
            "failovers": router.failovers,
            "tokens_resumed": router.tokens_resumed,
            "watchdog_trips": s1.watchdog_trips + s2.watchdog_trips,
            "lost_requests": len(failures),
            "match": got == want,
        }
        if failures:
            raise AssertionError(
                f"failover chaos lost {len(failures)} request(s) "
                f"(fired: {out['events_fired']}): {failures}")
        if not any(s == "router.dispatch" for s, _ in plan.fired):
            raise AssertionError(
                "failover chaos armed but no router.dispatch kill "
                "fired — widen the kill windows")
        if router.failovers == 0:
            raise AssertionError(
                "failover chaos completed without a single failover — "
                "the kills landed outside the streams")
        if router.tokens_resumed == 0:
            raise AssertionError(
                "every failover restarted from scratch — no resume "
                "carried drained tokens, so the journal's "
                "suffix-resume path never ran")
        if got != want:
            raise AssertionError(
                f"failover chaos divergence (fired: "
                f"{out['events_fired']}): {got} vs {want}")
        # ISSUE 12: SLO accounting survived the storm. Each of the
        # n_requests classified exactly once; the router's ITL sketch
        # holds exactly (tokens - 1) samples per request — a resume
        # that double-stamped its replayed prefix would inflate this,
        # a resume that dropped stamps would deflate it.
        slo_after = _slo_counts()
        if slo_after is not None:
            ttft_n = slo_after["ttft"] - slo_before["ttft"]
            itl_n = slo_after["itl"] - slo_before["itl"]
            cls_n = slo_after["classified"] - slo_before["classified"]
            want_itl = sum(len(w) - 1 for w in want)
            out["slo_ttft_samples"] = ttft_n
            out["slo_itl_samples"] = itl_n
            if ttft_n != len(want):
                raise AssertionError(
                    f"SLO ttft sketch holds {ttft_n} samples for "
                    f"{len(want)} requests — failover double- or "
                    "under-counted first tokens")
            if itl_n != want_itl:
                raise AssertionError(
                    f"SLO itl sketch holds {itl_n} samples, expected "
                    f"{want_itl} (tokens-1 per request): resumed "
                    "tokens were not counted exactly once")
            if cls_n != len(want):
                raise AssertionError(
                    f"bigdl_slo_requests_total classified {cls_n} "
                    f"requests, expected {len(want)}")
        return out
    finally:
        router.stop()
        w1.stop()
        w2.stop()
        s1.stop()
        s2.stop()


def run_api_chaos(seed: int = 0, n_requests: int = 3, kills: int = 1,
                  new_tokens: int = 5, smoke: bool = False) -> dict:
    """ISSUE 20 acceptance: the OpenAI gateway's SSE stream rides the
    failover journal, so a mid-stream ``router.dispatch`` kill under a
    live SSE client must be invisible at the ``data:`` boundary — the
    concatenated stream stays bit-identical to ``model.generate`` and
    every relayed token is stamped exactly once in the router's SLO
    sketches (the chunks and the stamps fire from the same journal
    drain event — one accounting, not two).

    Also asserts the disabled-mode contract: with the gate off the
    worker and router hold no gateway object, ``/v1/*`` answers 404
    naming ``bigdl.llm.api.enabled``, and serving a native request
    grows no ``bigdl_api_*`` metric series."""
    import numpy as np

    from bigdl_tpu import observability as obs
    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.llm.worker import LLMRouter, LLMWorker
    from tools.loadgen import _post_stream_openai

    if smoke:
        n_requests = min(n_requests, 2)
        new_tokens = min(new_tokens, 4)

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(0, 250, 8 + 2 * j).astype(np.int32)
               for j in range(n_requests)]
    want = [list(map(int,
                     model.generate(p[None],
                                    max_new_tokens=new_tokens)
                     [0, len(p):]))
            for p in prompts]

    def get(addr, path, timeout=60):
        import http.client
        import json as _json
        conn = http.client.HTTPConnection(*addr, timeout=timeout)
        try:
            conn.request("GET", path)
            r = conn.getresponse()
            return r.status, _json.loads(r.read().decode())
        finally:
            conn.close()

    # --- disabled-mode structural absence (gate off, one native req)
    s0 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8) \
        .start()
    w0 = LLMWorker(s0, role="decode").start()
    r0 = LLMRouter([], [w0.address], failover=True,
                   start_prober=False).start()
    before = set(obs.render().splitlines()) if obs.enabled() else set()
    try:
        assert w0._api is None and r0._api is None, \
            "disabled mode built a gateway object"
        for addr in (w0.address, r0.address):
            st, body = get(addr, "/v1/models")
            assert st == 404 and \
                "bigdl.llm.api.enabled" in body.get("error", ""), \
                f"disabled /v1/models answered {st}: {body}"
        st, body = _post_stream_openai(
            w0.address, {"prompt_ids": [1, 2, 3],
                         "max_new_tokens": 2}, 60)[:2]
        assert st == 404 and \
            "bigdl.llm.api.enabled" in body.get("error", ""), \
            f"disabled /v1/completions answered {st}: {body}"
        srv_out = s0.submit(prompts[0], max_new_tokens=2).get(
            timeout=600)
        assert len(srv_out) == 2, f"warmup answered {srv_out!r}"
        if obs.enabled():
            new = "\n".join(set(obs.render().splitlines()) - before)
            assert "bigdl_api_" not in new, \
                f"disabled mode grew gateway series: {new}"
    finally:
        r0.stop()
        w0.stop()
        s0.stop()

    # --- the storm: SSE client + mid-stream dispatch kill
    was_enabled = rel.enabled()
    if not was_enabled:
        rel.enable()
    s1 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                   kvcache=True, slo=True).start()
    s2 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                   kvcache=True, slo=True).start()
    w1 = LLMWorker(s1, role="decode").start()
    w2 = LLMWorker(s2, role="decode").start()
    router = LLMRouter([], [w1.address, w2.address], failover=True,
                       failover_attempts=8, start_prober=False,
                       slo=True, api=True).start()

    def _slo_counts():
        if not obs.enabled():
            return None
        reg = obs.REGISTRY
        return {
            "ttft": reg.sample_value("bigdl_router_ttft_seconds") or 0.0,
            "itl": reg.sample_value("bigdl_router_itl_seconds") or 0.0}
    slo_before = _slo_counts()
    try:
        # warm every storm shape on both engines (prefill + suffix
        # resume) so compiles don't eat the kill windows
        for srv in (s1, s2):
            for p in prompts:
                srv.submit(p, max_new_tokens=1).get(timeout=600)
                srv.submit(p, max_new_tokens=1).get(timeout=600)
        plan = rel.FaultPlan(seed=seed)
        for k in range(kills):
            plan.add("router.dispatch", "raise", times=1,
                     after=3 + 2 * k)
        plan.add("llm.step", "delay", times=None, delay=0.02)
        rel.set_plan(plan)
        got = []
        failures = []
        try:
            for j, p in enumerate(prompts):
                st, parsed, _, ttft, gaps = _post_stream_openai(
                    router.address,
                    {"prompt_ids": [int(t) for t in p],
                     "max_new_tokens": new_tokens}, 600)
                if st != 200 or parsed.get("error") is not None:
                    failures.append((j, st, parsed.get("error")))
                    got.append(None)
                else:
                    got.append(parsed["output_ids"])
        finally:
            rel.set_plan(None)
            if not was_enabled:
                rel.disable()
        out = {
            "seed": seed,
            "requests": n_requests,
            "events_fired": [f"{s}:{a}" for s, a in plan.fired],
            "failovers": router.failovers,
            "tokens_resumed": router.tokens_resumed,
            "lost_requests": len(failures),
            "match": got == want,
        }
        if failures:
            raise AssertionError(
                f"api chaos lost {len(failures)} request(s) "
                f"(fired: {out['events_fired']}): {failures}")
        if not any(s == "router.dispatch" for s, _ in plan.fired):
            raise AssertionError(
                "api chaos armed but no router.dispatch kill fired — "
                "widen the kill windows")
        if router.failovers == 0:
            raise AssertionError(
                "api chaos completed without a failover — the kill "
                "landed outside the SSE-relayed stream")
        if got != want:
            raise AssertionError(
                f"SSE stream divergence (fired: {out['events_fired']}"
                f"): {got} vs {want}")
        # the SSE boundary and the SLO sketches are ONE accounting:
        # exactly n first-token stamps and Σ(tokens-1) gap stamps for
        # the streamed requests, failover or not
        slo_after = _slo_counts()
        if slo_after is not None:
            ttft_n = slo_after["ttft"] - slo_before["ttft"]
            itl_n = slo_after["itl"] - slo_before["itl"]
            want_itl = sum(len(w) - 1 for w in want)
            out["slo_ttft_samples"] = ttft_n
            out["slo_itl_samples"] = itl_n
            if ttft_n != len(want):
                raise AssertionError(
                    f"SLO ttft sketch holds {ttft_n} samples for "
                    f"{len(want)} SSE requests — the relay double- or "
                    "under-stamped first tokens")
            if itl_n != want_itl:
                raise AssertionError(
                    f"SLO itl sketch holds {itl_n} samples, expected "
                    f"{want_itl}: SSE-relayed tokens were not stamped "
                    "exactly once")
        return out
    finally:
        router.stop()
        w1.stop()
        w2.stop()
        s1.stop()
        s2.stop()


def _counter_total(name: str) -> Optional[float]:
    """Sum of every child of one registry counter, or None when the
    observability registry is disabled (the flight cross-check then
    reconciles against the plain-int ledgers instead)."""
    from bigdl_tpu import observability as obs
    if not obs.enabled():
        return None
    total = 0.0
    for m in obs.REGISTRY.collect():
        if m.name == name:
            for _key, child in m.children():
                total += child.value
    return total


def _flight_tally() -> dict:
    """Flight-ring totals the reconciliation diffs: shed/failover event
    counts, Σ(evict event pages), and the ring's drop counter (a drop
    between the before/after snapshots would invalidate the diff)."""
    from bigdl_tpu.observability import flight
    r = flight.ring()
    evs = r.events() if r is not None else []
    return {
        "shed": sum(1 for e in evs if e["kind"] == "shed"),
        "failover": sum(1 for e in evs if e["kind"] == "failover"),
        "evict_pages": sum(e.get("detail", {}).get("pages", 0)
                           for e in evs if e["kind"] == "evict"),
        "dropped": r.dropped if r is not None else 0,
    }


def run_flight_chaos(seed: int = 0, new_tokens: int = 4,
                     smoke: bool = False) -> dict:
    """ISSUE 16 acceptance: the flight recorder under a failover storm.

    Part 1 — disabled mode is STRUCTURALLY absent. With
    ``bigdl.observability.flight.enabled`` off, ``flight.record`` is a
    no-op (the ring does not grow, the ``bigdl_flight_events_total``
    counter does not move, no new metric series appears in the
    registry) and both debug endpoints answer 404.

    Part 2 — with the recorder ON, a kill storm + pool-pressure replay
    + drain sheds, then the reconciliation: flight ``shed`` /
    ``failover`` events and Σ(``evict`` event pages) must match the
    ``bigdl_reliability_shed_total`` / ``bigdl_router_failovers_total``
    / ``bigdl_kvcache_evictions_total`` counter deltas EXACTLY. The
    events are emitted at the same call sites as the counter
    increments, so any drift means a forked emission path."""
    import http.client
    import json as _json

    import numpy as np

    from bigdl_tpu import observability as obs
    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.llm.worker import LLMRouter, LLMWorker
    from bigdl_tpu.observability import flight
    from bigdl_tpu.utils.conf import conf

    GATE = "bigdl.observability.flight.enabled"
    with conf._lock:
        prev = conf._set_layer.get(GATE)

    out = {"seed": seed, "gate": GATE}
    try:
        # --- part 1: disabled mode is structurally absent ---------------
        conf.set(GATE, "false")
        assert not flight.enabled, f"{GATE}=false left the recorder armed"
        before = _flight_tally()
        lines_before = (set(obs.render().splitlines())
                        if obs.enabled() else set())
        counter_before = _counter_total("bigdl_flight_events_total")
        flight.record("shed", request_id="chaos-probe",
                      component="chaos_probe")
        flight.record("evict", pages=3)
        for path in ("/debug/flight", "/debug/explain/chaos-probe"):
            resp = flight.debug_endpoint(path)
            assert resp is not None and resp[0] == 404, \
                f"{path} must 404 while {GATE} is off, got {resp!r}"
        after = _flight_tally()
        assert after == before, \
            f"record() grew the ring while {GATE} was off: {after}"
        assert _counter_total("bigdl_flight_events_total") \
            == counter_before, \
            f"bigdl_flight_events_total moved while {GATE} was off"
        if obs.enabled():
            grown = {ln.split("{")[0].split(" ")[0]
                     for ln in set(obs.render().splitlines())
                     - lines_before}
            assert not any("flight" in g for g in grown), \
                f"disabled mode grew flight series: {grown}"
        out["disabled_mode"] = "structurally absent"

        # --- part 2: the storm, recorder on -----------------------------
        conf.set(GATE, "true")
        assert flight.enabled
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                             max_cache_len=128)
        rs = np.random.RandomState(seed)
        storm_prompts = [rs.randint(0, 250, 10 + 2 * j).astype(np.int32)
                         for j in range(2)]
        shared = rs.randint(0, 250, 12).astype(np.int32)
        evict_prompts = [np.concatenate(
            [shared, rs.randint(0, 250, 2 + j % 5).astype(np.int32)])
            for j in range(3 if smoke else 6)]

        was_enabled = rel.enabled()
        if not was_enabled:
            rel.enable()
        # small pool (the kvcache pass's sizing) so the shared-prefix
        # replay genuinely evicts; kills tear the router->worker stream
        # mid-decode so the journal resume path genuinely fires
        s1 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                       num_pages=7, kvcache=True).start()
        s2 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                       num_pages=7, kvcache=True).start()
        w1 = LLMWorker(s1, role="decode").start()
        w2 = LLMWorker(s2, role="decode").start()
        router = LLMRouter([], [w1.address, w2.address], failover=True,
                           failover_attempts=8, start_prober=False) \
            .start()
        try:
            # warm the storm shapes on both engines (resume re-prefills
            # prompt+generated through the partial-prefill shape)
            for srv in (s1, s2):
                for p in storm_prompts:
                    srv.submit(p, max_new_tokens=1).get(timeout=600)
                    srv.submit(p, max_new_tokens=1).get(timeout=600)

            t_before = _flight_tally()
            c_before = {
                "shed": _counter_total("bigdl_reliability_shed_total"),
                "failover": _counter_total(
                    "bigdl_router_failovers_total"),
                "evict": _counter_total(
                    "bigdl_kvcache_evictions_total"),
            }
            fo_before = router.failovers
            ev_before = s1._kv.evictions + s2._kv.evictions

            plan = rel.FaultPlan(seed=seed)
            plan.add("router.dispatch", "raise", times=1, after=3)
            plan.add("llm.step", "delay", times=None, delay=0.02)
            rel.set_plan(plan)
            try:
                for p in storm_prompts:
                    conn = http.client.HTTPConnection(*router.address,
                                                      timeout=600)
                    try:
                        conn.request(
                            "POST", "/worker_generate",
                            _json.dumps({
                                "prompt_ids": [int(t) for t in p],
                                "max_new_tokens": new_tokens}),
                            {"Content-Type": "application/json"})
                        r = conn.getresponse()
                        body = _json.loads(r.read().decode())
                        assert r.status == 200, body
                    finally:
                        conn.close()
            finally:
                rel.set_plan(None)
            # pool-pressure replay: shared-prefix chains past the
            # 7-page pool force radix evictions (flight "evict" events)
            reqs = [s1.submit(p, max_new_tokens=new_tokens)
                    for p in evict_prompts]
            for r in reqs:
                r.get(timeout=600)
            # drain sheds: begin_drain flips the admission arm that
            # emits the shed event + counter at one shared site
            s1.begin_drain()
            sheds_forced = 0
            for p in storm_prompts:
                try:
                    s1.submit(p, max_new_tokens=1)
                except rel.OverloadError:
                    sheds_forced += 1
            s1.cancel_drain()
            assert sheds_forced == len(storm_prompts), \
                "draining engine accepted a submit"

            # one live HTTP probe: the worker surface serves the ring
            conn = http.client.HTTPConnection(*w1.address, timeout=60)
            try:
                conn.request("GET", "/debug/flight?kind=evict")
                r = conn.getresponse()
                ring_doc = _json.loads(r.read().decode())
                assert r.status == 200, ring_doc
                assert ring_doc["events"], \
                    "GET /debug/flight?kind=evict returned no events"
            finally:
                conn.close()

            t_after = _flight_tally()
            c_after = {
                "shed": _counter_total("bigdl_reliability_shed_total"),
                "failover": _counter_total(
                    "bigdl_router_failovers_total"),
                "evict": _counter_total(
                    "bigdl_kvcache_evictions_total"),
            }
            fo_delta = router.failovers - fo_before
            ev_delta = s1._kv.evictions + s2._kv.evictions - ev_before
            assert t_after["dropped"] == t_before["dropped"], \
                "ring dropped events mid-check; raise " \
                "bigdl.observability.flight.capacity"
            deltas = {k: t_after[k] - t_before[k]
                      for k in ("shed", "failover", "evict_pages")}
            out.update(events=deltas, failovers=fo_delta,
                       evicted_pages=ev_delta,
                       events_fired=[f"{s}:{a}" for s, a in plan.fired])
            if fo_delta == 0:
                raise AssertionError(
                    "flight chaos storm completed without a failover — "
                    "the kill landed outside the streams")
            if ev_delta == 0:
                raise AssertionError(
                    "flight chaos replay forced no evictions — the "
                    "pool was not under pressure; shrink it")
            # the reconciliation: EXACT, no tolerance — shared call
            # sites mean any drift is a forked emission path
            if deltas["failover"] != fo_delta:
                raise AssertionError(
                    f"{deltas['failover']} flight failover events vs "
                    f"{fo_delta} journal failovers")
            if deltas["evict_pages"] != ev_delta:
                raise AssertionError(
                    f"flight evict events carry {deltas['evict_pages']} "
                    f"pages vs {ev_delta} ledger evictions")
            if deltas["shed"] < sheds_forced:
                raise AssertionError(
                    f"{sheds_forced} sheds forced but only "
                    f"{deltas['shed']} flight shed events recorded")
            if c_before["shed"] is not None:
                for key, counter in (("shed", "shed"),
                                     ("failover", "failover"),
                                     ("evict_pages", "evict")):
                    got = c_after[counter] - c_before[counter]
                    if deltas[key] != got:
                        raise AssertionError(
                            f"flight {key} events ({deltas[key]}) != "
                            f"bigdl_*_total counter delta ({got})")
                out["counters_reconciled"] = True
            else:
                out["counters_reconciled"] = "obs disabled: ledger-only"
        finally:
            rel.set_plan(None)
            if not was_enabled:
                rel.disable()
            router.stop()
            w1.stop()
            w2.stop()
            s1.stop()
            s2.stop()
    finally:
        if prev is None:
            conf.unset(GATE)
        else:
            conf.set(GATE, prev)
    out["match"] = True
    return out


def run_alerts_chaos(seed: int = 0, new_tokens: int = 3,
                     smoke: bool = False) -> dict:
    """ISSUE 18 acceptance: the time-series plane + alert engine under
    a seeded failover storm.

    Part 1 — disabled mode is STRUCTURALLY absent. With
    ``bigdl.observability.timeseries.enabled`` off, ``acquire()``
    builds nothing, no sampler thread exists, no
    ``bigdl_timeseries_*`` / ``bigdl_alerts_*`` series appears, and
    ``/metrics/query``, ``/fleet/timeline`` and ``/alerts`` all answer
    404 naming the gate key.

    Part 2 — plane ON with a tiny-window fast-burn rule installed
    through the declarative ``bigdl.observability.alerts.rules`` path:
    clean traffic keeps the rule inactive; a seeded failover storm
    (mid-stream ``router.dispatch`` kill + ``llm.step`` delays pushing
    every request past the TTFT objective) must flip it to firing on
    the FIRST store sample after the storm (one evaluation interval),
    hold firing while the storm is still inside both windows, and
    resolve once the windows drain past it under clean recovery
    traffic. Alert state transitions must reconcile EXACTLY with the
    flight ``alert_fire`` / ``alert_resolve`` events (same call site)
    and with the ``bigdl_alerts_transitions_total`` counter deltas.

    Part 3 — the autoscaler reads its shed-pressure signal through the
    store's :class:`~bigdl_tpu.observability.timeseries.WindowedCounter`
    primitive now; replaying the OLD summed-delta formula over the
    controller's recorded ``sheds_by`` traces must yield the identical
    pressure/idle/action sequence on restart-free traces (the
    per-member primitive only diverges where the old clamp was wrong:
    a member restart no longer swallows the other members' sheds)."""
    import http.client
    import json as _json
    import threading
    from urllib.parse import quote

    import numpy as np

    from bigdl_tpu import observability as obs
    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.fleet import FleetController
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.llm.worker import LLMRouter, LLMWorker
    from bigdl_tpu.observability import alerts, flight
    from bigdl_tpu.observability import timeseries as ts
    from bigdl_tpu.utils.conf import conf

    GATE = "bigdl.observability.timeseries.enabled"
    KEYS = (GATE, "bigdl.observability.timeseries.interval",
            "bigdl.observability.alerts.rules",
            "bigdl.observability.flight.enabled")
    with conf._lock:
        prev = {k: conf._set_layer.get(k) for k in KEYS}

    def post(addr, path, body, timeout=600):
        conn = http.client.HTTPConnection(*addr, timeout=timeout)
        try:
            conn.request("POST", path, _json.dumps(body),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, _json.loads(r.read().decode())
        finally:
            conn.close()

    def get(addr, path, timeout=60):
        conn = http.client.HTTPConnection(*addr, timeout=timeout)
        try:
            conn.request("GET", path)
            r = conn.getresponse()
            return r.status, _json.loads(r.read().decode())
        finally:
            conn.close()

    def _alert_events():
        r = flight.ring()
        evs = r.events() if r is not None else []
        return {"fire": sum(1 for e in evs if e["kind"] == "alert_fire"),
                "resolve": sum(1 for e in evs
                               if e["kind"] == "alert_resolve")}

    RULE = "chaos-fast-burn-ttft"

    def _trans(state):
        if not obs.enabled():
            return 0.0
        return obs.REGISTRY.sample_value(
            "bigdl_alerts_transitions_total", rule=RULE,
            state=state) or 0.0

    out = {"seed": seed, "gate": GATE}
    try:
        # --- part 1: disabled mode is structurally absent ---------------
        conf.set(GATE, "false")
        assert not ts.enabled, f"{GATE}=false left the plane armed"
        lines_before = (set(obs.render().splitlines())
                        if obs.enabled() else set())
        assert ts.acquire() is None, \
            "acquire() built a store while the gate was off"
        for path in ("/metrics/query?series=bigdl_slo_requests_total"
                     "&window=60",
                     "/fleet/timeline?series=bigdl_slo_requests_total"):
            resp = ts.debug_endpoint(path)
            assert resp is not None and resp[0] == 404 \
                and resp[1].get("gate") == GATE, \
                f"{path} must 404 naming {GATE} while off, got {resp!r}"
        resp = alerts.debug_endpoint("/alerts")
        assert resp is not None and resp[0] == 404 \
            and resp[1].get("gate") == GATE, \
            f"/alerts must 404 naming {GATE} while off, got {resp!r}"
        assert not [t for t in threading.enumerate()
                    if t.name == ts.TimeSeriesStore.THREAD_NAME], \
            "disabled mode has a live sampler thread"
        if obs.enabled():
            grown = set(obs.render().splitlines()) - lines_before
            leaked = [g for g in grown
                      if "bigdl_timeseries" in g or "bigdl_alerts" in g]
            assert not leaked, \
                f"disabled mode grew time-series series: {leaked}"
        out["disabled_mode"] = "structurally absent"

        # --- part 2: the storm, plane + alert engine on -----------------
        conf.set(GATE, "true")
        # park the wall-clock sampler: every sample below is a manual
        # fake-clock tick, and a stray real-time sample (ts ~ 1.7e9)
        # would evict the whole fake-ts ring through retention
        conf.set("bigdl.observability.timeseries.interval", "3600")
        conf.set("bigdl.observability.flight.enabled", "true")
        rules = [{"name": RULE, "kind": "burn_rate", "slo": "ttft",
                  "short": 6.0, "long": 12.0, "factor": 5.0}]
        conf.set("bigdl.observability.alerts.rules", _json.dumps(rules))
        assert ts.enabled

        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                             max_cache_len=128)
        rs = np.random.RandomState(seed)
        n_storm = 2 if smoke else 3
        prompts = [rs.randint(0, 250, 10 + 2 * j).astype(np.int32)
                   for j in range(n_storm)]

        was_enabled = rel.enabled()
        if not was_enabled:
            rel.enable()
        s1 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                       kvcache=True, slo=True).start()
        s2 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                       kvcache=True, slo=True).start()
        w1 = LLMWorker(s1, role="decode").start()
        w2 = LLMWorker(s2, role="decode").start()
        router = LLMRouter([], [w1.address, w2.address], failover=True,
                           failover_attempts=8, start_prober=False,
                           slo=True).start()
        try:
            st = ts.store()
            eng = alerts.engine()
            assert st is not None and eng is not None, \
                "plane on but acquire() built no store/engine"
            assert [r["name"] for r in eng.rules] == [RULE], \
                "declarative rules override did not replace built-ins"
            assert [t for t in threading.enumerate()
                    if t.name == ts.TimeSeriesStore.THREAD_NAME], \
                "plane on but no sampler thread"

            # warm every storm shape on both engines (resume re-prefills
            # through the partial-prefill shape; an unwarmed compile
            # would smear real seconds into the TTFT the storm asserts)
            for srv in (s1, s2):
                for p in prompts:
                    srv.submit(p, max_new_tokens=1).get(timeout=600)
                    srv.submit(p, max_new_tokens=1).get(timeout=600)

            ev_before = _alert_events()
            tr_before = {s: _trans(s) for s in ("firing", "resolved")}

            def serve(p):
                stt, body = post(router.address, "/worker_generate",
                                 {"prompt_ids": [int(t) for t in p],
                                  "max_new_tokens": new_tokens})
                assert stt == 200, body

            # clean phase: fast traffic, rule must stay inactive
            st.sample_now(now=0.0)
            for p in prompts[:2]:
                serve(p)
            st.sample_now(now=2.0)
            st.sample_now(now=4.0)
            assert eng.firing() == [], \
                f"clean traffic fired {eng.firing()}"

            # the storm: a mid-stream dispatch kill (failover resumes
            # it) + per-step delays pushing every TTFT past the 500 ms
            # objective on both the engine and the router scope
            plan = rel.FaultPlan(seed=seed)
            plan.add("router.dispatch", "raise", times=1, after=1)
            plan.add("llm.step", "delay", times=None, delay=0.6)
            rel.set_plan(plan)
            try:
                for p in prompts:
                    serve(p)
            finally:
                rel.set_plan(None)
            fired_at = st.sample_now(now=6.0)
            assert RULE in eng.firing(), \
                "fast-burn rule not firing on the first evaluation " \
                f"after the storm: {eng.status()}"
            out["fired_at"] = fired_at
            out["events_fired"] = [f"{s}:{a}" for s, a in plan.fired]

            # live surfaces while firing (the HTTP arms default `now`
            # to wall clock, so the windows must reach back to the
            # fake-clock sample timestamps)
            stt, body = get(w1.address, "/alerts")
            assert stt == 200 and RULE in body["firing"], body
            q = quote('bigdl_slo_requests_total{slo="ttft",'
                      'verdict="violated"}', safe="")
            stt, body = get(router.address,
                            f"/metrics/query?series={q}&window=1e15"
                            "&fn=delta")
            assert stt == 200 and (body["value"] or 0) > 0, body
            stt, body = get(router.address,
                            "/fleet/timeline?series="
                            "bigdl_slo_requests_total&window=1e15")
            assert stt == 200 and body["merged"], body
            if obs.enabled():
                assert (obs.REGISTRY.sample_value("bigdl_alerts_firing")
                        or 0) >= 1, "bigdl_alerts_firing gauge not set"

            # storm deltas still inside both windows: one clean sample
            # must NOT flap the alert off (the long window's job)
            serve(prompts[0])
            st.sample_now(now=8.0)
            assert RULE in eng.firing(), \
                "alert flapped off while the storm was in-window"

            # recovery: windows drain past the storm; clean traffic
            # between the next ticks evaluates to zero burn
            st.sample_now(now=30.0)
            for p in prompts[:2]:
                serve(p)
            st.sample_now(now=32.0)
            assert eng.firing() == [], \
                f"alert did not resolve after recovery: {eng.status()}"
            rule_st = [r for r in eng.status()["rules"]
                       if r["name"] == RULE][0]
            assert rule_st["state"] == "resolved", rule_st

            # the reconciliation: transitions == flight events, EXACTLY
            ev_delta = {k: _alert_events()[k] - ev_before[k]
                        for k in ev_before}
            tr_delta = {s: _trans(s) - tr_before[s]
                        for s in ("firing", "resolved")}
            assert ev_delta == {"fire": 1, "resolve": 1}, \
                f"flight alert events off: {ev_delta}"
            if obs.enabled():
                assert tr_delta == {"firing": 1.0, "resolved": 1.0}, \
                    f"transition counters off: {tr_delta}"
                out["transitions"] = tr_delta
            out["alert_events"] = ev_delta
            out["sample_overhead_us"] = st.status()["sample_overhead_us"]
        finally:
            rel.set_plan(None)
            if not was_enabled:
                rel.disable()
            router.stop()
            w1.stop()
            w2.stop()
            s1.stop()
            s2.stop()

        # --- part 3: autoscaler decision identity -----------------------
        # same synthesized restart-free trace through (a) a live
        # FleetController reading the WindowedCounter primitive and
        # (b) a replay of the old summed max(total-last, 0) formula —
        # pressure/idle/action must be IDENTICAL tick for tick
        class _StubRouter:
            def __init__(self):
                self._pool_lock = threading.Lock()
                self.decode_workers = [("stub", 1), ("stub", 2)]

        def _sig(sheds_by, queue, active, workers):
            return {"workers": workers, "queue": queue, "active": active,
                    "inflight": 0, "sheds": sum(sheds_by.values()),
                    "sheds_by": dict(sheds_by), "occupancy_max": 0.0,
                    "queue_interactive": 0.0, "parked_by": {}}

        trace = [
            _sig({"a:1": 0.0, "b:1": 0.0}, 0.0, 1.0, 2),
            _sig({"a:1": 2.0, "b:1": 0.0}, 0.0, 1.0, 2),  # sheds grew
            _sig({"a:1": 2.0, "b:1": 3.0}, 5.0, 1.0, 2),  # grew + queue
            _sig({"a:1": 2.0, "b:1": 3.0}, 0.0, 1.0, 2),  # flat
            _sig({"a:1": 2.0}, 0.0, 0.0, 1),              # b departs flat
            _sig({"a:1": 2.0}, 0.0, 0.0, 1),              # idle, n == min
        ]
        ctl = FleetController(_StubRouter(), min_workers=1,
                              max_workers=4, sustain=2, cooldown=0.0,
                              queue_high=2.0, idle_low=0.0)
        it = iter(trace)
        ctl.signals = lambda: next(it)
        for _ in trace:
            ctl.tick()
        legacy = []
        last_sum = None
        hot = cold = 0
        for sig in trace:
            total = sum(sig["sheds_by"].values())
            delta = 0.0 if last_sum is None \
                else max(total - last_sum, 0.0)
            last_sum = total
            n = sig["workers"]
            pressure = (sig["queue"] > ctl.queue_high * max(n, 1)
                        or delta > 0
                        or (n > 0 and sig["occupancy_max"] > 0.9)
                        or (ctl.pressure_interactive
                            and sig["queue_interactive"]
                            > ctl.queue_high))
            idle = (sig["queue"] + sig["active"]
                    + sig["inflight"]) <= ctl.idle_low
            if pressure:
                hot += 1
                cold = 0
            elif idle:
                cold += 1
                hot = 0
            else:
                hot = cold = 0
            action = "none"
            if pressure and hot >= ctl.sustain and n < ctl.max_workers:
                action = "scale_out"
                hot = 0
            elif idle and cold >= ctl.sustain and n > ctl.min_workers:
                action = "scale_in"
                cold = 0
            legacy.append({"shed_delta": delta, "pressure": pressure,
                           "idle": idle, "action": action})
        got = [{k: d[k] for k in ("shed_delta", "pressure", "idle",
                                  "action")} for d in ctl.decisions]
        if got != legacy:
            raise AssertionError(
                "autoscaler diverged from the legacy shed-delta "
                f"formula on a restart-free trace:\n new={got}\n "
                f"old={legacy}")
        assert [d["action"] for d in got].count("scale_out") == 1, got
        # where the primitive intentionally differs: a member restart
        # is a reset for THAT member (its post-restart count is the
        # delta), not a clamp that swallows every other member's sheds
        wc = ts.WindowedCounter()
        assert wc.observe({"m": 10.0}) == 0.0
        assert wc.observe({"m": 14.0}) == 4.0
        assert wc.observe({"m": 3.0}) == 3.0
        out["autoscaler_decisions"] = "identical"
    finally:
        for k, v in prev.items():
            if v is None:
                conf.unset(k)
            else:
                conf.set(k, v)
        ts.reset()
        alerts.reset()
    out["match"] = True
    return out


def run_fleet_chaos(seed: int = 0, smoke: bool = False) -> dict:
    """ISSUE 15 acceptance: the elastic-fleet soak. A fleet-enabled
    router (autoscaler + graceful drain) over a
    :class:`LocalWorkerProvider` pool is driven by the closed-loop
    load generator (tools/loadgen.py) through spike → scale-out →
    worker KILLED mid-drain → scale-in cycles, with a seeded mid-stream
    ``router.dispatch`` kill and ``worker.drain`` delays widening the
    drain windows. The contract:

    - **zero lost requests** across every phase (sheds retry, failures
      fail over, drains bounce — none of it reaches the client);
    - greedy outputs **bit-identical** to ``model.generate`` goldens;
    - a gracefully drained worker's warm KV chains land on the
      survivor and serve **prefix hits** there (asserted via a chain
      only the drained worker held);
    - the pool **converges** back to ``min`` workers;
    - ``bigdl.llm.fleet.enabled=false`` is structurally absent: no
      drain coordinator, no controller thread, no ``bigdl_fleet_*``
      series, ``/worker_drain`` and ``/fleet/autoscaler`` answer 404.

    ``smoke=True`` shrinks the request counts (same phases, same
    assertions) for the bench telemetry block."""
    import threading
    import time as _time

    import numpy as np

    from bigdl_tpu import observability as obs
    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.llm.worker import LLMRouter, LLMWorker
    from bigdl_tpu.utils.conf import conf
    from tools.loadgen import gen_prompts, run_load

    n_requests = 6 if smoke else 8
    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    prompts = gen_prompts(n_requests, seed=seed, shared_prefix=16)
    budgets = [2 + 2 * (j % 2) for j in range(n_requests)]
    want = [list(map(int,
                     model.generate(p[None], max_new_tokens=b)
                     [0, len(p):]))
            for p, b in zip(prompts, budgets)]

    def get(addr, path):
        import http.client
        import json as _json
        conn = http.client.HTTPConnection(*addr, timeout=5)
        try:
            conn.request("GET", path)
            r = conn.getresponse()
            return r.status, _json.loads(r.read().decode())
        finally:
            conn.close()

    # --- disabled-mode structural absence (bigdl.llm.fleet.enabled
    # off, the default): no drain coordinator, endpoints 404, no
    # controller thread, no bigdl_fleet_* series
    s0 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8)
    w0 = LLMWorker(s0, role="decode").start()
    before = set(obs.render().splitlines()) if obs.enabled() else set()
    r0 = LLMRouter([], [w0.address], failover=True,
                   start_prober=False).start()
    try:
        assert w0._drain is None, "fleet-off worker built a drain"
        assert r0._fleet is None, "fleet-off router built a controller"
        st, _ = get(w0.address, "/worker_drain")
        assert st == 404, f"/worker_drain answered {st} with fleet off"
        st, _ = get(r0.address, "/fleet/autoscaler")
        assert st == 404, f"/fleet/autoscaler answered {st} fleet-off"
        if obs.enabled():
            grown = "\n".join(set(obs.render().splitlines()) - before)
            assert "bigdl_fleet_" not in grown, \
                f"fleet-off mode grew fleet series:\n{grown}"
        assert not [t for t in threading.enumerate()
                    if t.name.startswith(("bigdl-fleet",))], \
            "fleet-off mode started a fleet thread"
    finally:
        r0.stop()
        w0.stop()
        s0.stop(drain=False)

    # --- the soak
    from bigdl_tpu.llm.fleet import LocalWorkerProvider
    with conf._lock:
        prev_sync = conf._set_layer.get("bigdl.llm.kvtier.sync")
    conf.set("bigdl.llm.kvtier.sync", "true")   # inline migrations:
    was_enabled = rel.enabled()                 # deterministic spills
    if not was_enabled:
        rel.enable()
    provider = LocalWorkerProvider(
        model, server_kwargs=dict(
            max_batch=2, max_seq_len=64, page_size=8, num_pages=24,
            kvcache=True, kvtier=True, host_pages=64, max_queue=8))
    router = None
    plan = rel.FaultPlan(seed=seed)
    try:
        seed_addr = provider.launch()
        seed_srv = provider.servers()[seed_addr]
        # warm every served shape (full prefill buckets + the partial
        # suffix shapes resumes and prefix hits use); the compiled-step
        # cache is shared across engines, so scaled-out workers reuse
        # these programs
        for p, b in zip(prompts, budgets):
            seed_srv.submit(p, max_new_tokens=b).get(timeout=600)
            seed_srv.submit(p, max_new_tokens=b).get(timeout=600)
        router = LLMRouter(
            [], [seed_addr], failover=True, failover_attempts=8,
            start_prober=False, fleet=True, provider=provider,
            start_fleet=False, fleet_opts=dict(
                min_workers=1, max_workers=3, interval=0.05,
                cooldown=0.0, sustain=1, queue_high=1.0, idle_low=0.0,
                drain_timeout=20.0)).start()
        fleet = router._fleet

        def tick_until(cond, timeout):
            t0 = _time.time()
            while _time.time() - t0 < timeout:
                fleet.tick()
                if cond():
                    return True
                _time.sleep(0.02)
            return False

        def pool_size():
            with router._pool_lock:
                return len(router.decode_workers)

        # one mid-stream connection kill (the journal-resume path) +
        # per-chain drain delays (widens the mid-drain kill window)
        plan.add("router.dispatch", "raise", times=1, after=6)
        plan.add("worker.drain", "delay", times=None, delay=0.05)
        rel.set_plan(plan)

        lost = 0
        results = {}

        def load_phase(name, qps):
            out = {}

            def run():
                out["res"] = run_load(router.address, prompts,
                                      max_new_tokens=budgets, qps=qps,
                                      concurrency=4)
            t = threading.Thread(target=run, daemon=True)
            t.start()
            return t, out

        # phase A: spike against one worker -> sustained queue
        # pressure -> scale-out; a seeded mid-stream kill fails over
        t, holder = load_phase("spike", qps=200.0)
        scaled = tick_until(lambda: pool_size() >= 2, timeout=30.0)
        t.join(timeout=600)
        res_a = holder["res"]
        results["spike"] = {k: res_a[k] for k in
                            ("sent", "ok", "lost", "retries_503")}
        lost += res_a["lost"]
        if not scaled:
            raise AssertionError(
                "fleet soak: the load spike never scaled the pool out "
                f"(signals: {fleet.signals()})")
        if res_a["outputs"] != want:
            raise AssertionError(
                f"fleet soak divergence in the spike phase: "
                f"{res_a['outputs']} vs {want}")

        # phase B: idle -> scale-in begins -> KILL the victim
        # mid-drain; the controller must remove the corpse, losing
        # nothing (its in-flight was already drained, its chains
        # re-prefill)
        if not tick_until(lambda: fleet._draining is not None,
                          timeout=30.0):
            raise AssertionError(
                "fleet soak: idle pool never began a scale-in drain")
        victim = tuple(fleet._draining["addr"])
        deadline = _time.time() + 10.0
        while _time.time() < deadline:
            try:
                _st, body = get(victim, "/worker_drain")
            except Exception:   # noqa: BLE001
                break
            if body.get("state") in ("migrating", "drained"):
                break
            _time.sleep(0.01)
        provider.kill(victim)
        if not tick_until(lambda: fleet._draining is None, timeout=30.0):
            raise AssertionError(
                "fleet soak: the controller never resolved the "
                "killed-mid-drain worker")
        if fleet.drains_lost < 1:
            raise AssertionError(
                "fleet soak: the mid-drain kill was not observed as a "
                f"lost drain (events: {fleet.events[-8:]})")

        # phase C: spike again -> scale out; plant a chain ONLY the
        # new worker holds; idle -> GRACEFUL drain must migrate it to
        # the survivor, where it serves a prefix hit
        t, holder = load_phase("respike", qps=200.0)
        scaled = tick_until(lambda: pool_size() >= 2, timeout=30.0)
        t.join(timeout=600)
        res_c = holder["res"]
        results["respike"] = {k: res_c[k] for k in
                              ("sent", "ok", "lost", "retries_503")}
        lost += res_c["lost"]
        if not scaled:
            raise AssertionError(
                "fleet soak: the second spike never scaled out")
        if res_c["outputs"] != want:
            raise AssertionError(
                f"fleet soak divergence in the respike phase: "
                f"{res_c['outputs']} vs {want}")
        with router._pool_lock:
            newbie = tuple(router.decode_workers[-1])
        if newbie == seed_addr:
            raise AssertionError("fleet soak: LIFO victim selection "
                                 "would drain the seed worker")
        rs = np.random.RandomState(seed + 1234)
        unique = rs.randint(0, 250, 24).astype(np.int32)
        new_srv = provider.servers()[newbie]
        new_srv.submit(unique, max_new_tokens=2).get(timeout=600)
        reused_before = seed_srv._kv.prefix_tokens_reused
        if not tick_until(
                lambda: fleet.scale_ins >= 1 and pool_size() == 1,
                timeout=60.0):
            raise AssertionError(
                "fleet soak: the graceful scale-in never converged "
                f"(events: {fleet.events[-8:]})")
        graceful = [e for e in fleet.events
                    if e["action"] == "scale_in"
                    and e.get("outcome") == "drained"]
        if not graceful or not any(e.get("chains", 0) > 0
                                   for e in graceful):
            raise AssertionError(
                "fleet soak: the graceful drain migrated no warm KV "
                f"chains (events: {fleet.events[-8:]})")
        # the migrated chain serves a prefix hit on the survivor
        seed_srv.submit(unique, max_new_tokens=2).get(timeout=600)
        reused_after = seed_srv._kv.prefix_tokens_reused
        if reused_after <= reused_before:
            raise AssertionError(
                "fleet soak: the survivor served no prefix hit from "
                "the drained worker's migrated chains "
                f"(reused {reused_before} -> {reused_after})")

        if not any(s == "router.dispatch" for s, _ in plan.fired):
            raise AssertionError(
                "fleet soak armed but the mid-stream router.dispatch "
                "kill never fired — widen the kill window")
        if lost:
            raise AssertionError(
                f"fleet soak lost {lost} request(s): {results}")
        # the engine ledger is back to idle on the survivor (every
        # page charge returned across all the churn)
        idle_budget = seed_srv._budget_avail
        out = {
            "seed": seed,
            "requests_per_phase": n_requests,
            "phases": results,
            "events_fired": [f"{s}:{a}" for s, a in plan.fired],
            "scale_outs": fleet.scale_outs,
            "scale_ins": fleet.scale_ins,
            "drains_lost": fleet.drains_lost,
            "chains_migrated": sum(e.get("chains", 0)
                                   for e in graceful),
            "failovers": router.failovers,
            "converged_workers": pool_size(),
            "survivor_idle_budget": idle_budget,
            "lost_requests": lost,
            "match": True,
        }
        return out
    finally:
        rel.set_plan(None)
        if not was_enabled:
            rel.disable()
        if router is not None:
            router.stop()
        provider.stop_all()
        if prev_sync is None:
            conf.unset("bigdl.llm.kvtier.sync")
        else:
            conf.set("bigdl.llm.kvtier.sync", prev_sync)


def run_preempt_chaos(seed: int = 0, smoke: bool = False) -> dict:
    """ISSUE 17 acceptance: the priority storm. Sustained batch-class
    decodes saturate every slot; an interactive burst arrives; the
    SLO-class scheduler must preempt batch victims LOSSLESSLY — with
    seeded ``llm.preempt`` faults aborting preemption attempts
    mid-decision — and every request (preempted or not) must complete
    with greedy output bit-identical to its unpreempted
    ``model.generate`` golden, zero lost. The flight-recorder
    ``preempt``/``preempt_resume`` events, the
    ``bigdl_llm_preemptions_total`` counter, and the engine's plain-int
    ledgers must reconcile EXACTLY, the KV ledger/arena must return to
    idle, and interactive TTFT must be measurably better than the same
    storm with the scheduler off (FIFO).

    Also asserts the disabled-mode contract: with
    ``bigdl.llm.priority.enabled`` off (the default) the engine builds
    no scheduler objects, mints no priority metric series, and serves
    the identical storm FIFO bit-identical — the class stamp is carried
    but inert."""
    import time as _time

    import numpy as np

    from bigdl_tpu import observability as obs
    from bigdl_tpu import reliability as rel
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.llm.serving import LLMServer
    from bigdl_tpu.observability import flight
    from bigdl_tpu.utils.conf import conf

    GATE = "bigdl.llm.priority.enabled"
    FLIGHT_GATE = "bigdl.observability.flight.enabled"
    n_batch = 3 if smoke else 4
    n_inter = 2 if smoke else 4
    # the victim budget sets the FIFO baseline's slot-turnover time;
    # the preempted path's TTFT is independent of it, so a long batch
    # budget is what makes "measurably better" robust to CI jitter
    batch_budget = 16
    inter_budget = 3
    num_pages = 32

    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                         max_cache_len=128)
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, 250, 8).astype(np.int32)
    batch_prompts = [np.concatenate(
        [shared, rs.randint(0, 250, 6 + 2 * (j % 3)).astype(np.int32)])
        for j in range(n_batch)]
    inter_prompts = [rs.randint(0, 250, 6 + j % 4).astype(np.int32)
                     for j in range(n_inter)]
    prompts = batch_prompts + inter_prompts
    budgets = [batch_budget] * n_batch + [inter_budget] * n_inter
    classes = ["batch"] * n_batch + ["interactive"] * n_inter
    want = [list(map(int,
                     model.generate(p[None], max_new_tokens=b)
                     [0, len(p):]))
            for p, b in zip(prompts, budgets)]

    def storm(priority: bool):
        """One storm: saturate the 2 slots with batch decodes, then
        burst the interactive prompts. Returns (outputs-in-submit-
        order, interactive TTFTs, server) — the server already
        stopped, so its ledgers are post-drain."""
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=num_pages, kvcache=True, kvtier=True,
                        host_pages=64, priority=priority).start()
        try:
            b_reqs = [srv.submit(p, max_new_tokens=batch_budget,
                                 priority="BATCH")     # case-insensitive
                      for p in batch_prompts]
            # the burst must land while batch decodes hold every slot —
            # wait for first tokens, not just admission
            deadline = _time.time() + 120.0
            while _time.time() < deadline and \
                    sum(1 for r in b_reqs if len(r.tokens) >= 1) < 2:
                _time.sleep(0.005)
            i_reqs = [srv.submit(p, max_new_tokens=inter_budget,
                                 priority="interactive")
                      for p in inter_prompts]
            outs = [list(map(int, r.get(timeout=600)))
                    for r in b_reqs + i_reqs]
            ttfts = [r.t_first_token - r.t_submit for r in i_reqs
                     if r.t_first_token]
        finally:
            srv.stop()
        return outs, ttfts, srv

    with conf._lock:
        prev_sync = conf._set_layer.get("bigdl.llm.kvtier.sync")
        prev_flight = conf._set_layer.get(FLIGHT_GATE)
    conf.set("bigdl.llm.kvtier.sync", "true")   # inline migrations:
    was_enabled = rel.enabled()                 # deterministic spills
    if not was_enabled:
        rel.enable()
    try:
        # --- part 1: disabled mode (the conf default) is structurally
        # absent — no scheduler objects, no priority series, and the
        # storm serves FIFO bit-identical with the class stamp inert
        lines_before = (set(obs.render().splitlines())
                        if obs.enabled() else set())
        srv0 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                         num_pages=num_pages, kvcache=True).start()
        try:
            assert srv0._sched is None and srv0._parked is None, \
                f"{GATE} off (the default) built scheduler state"
            reqs0 = [srv0.submit(p, max_new_tokens=b, priority=c)
                     for p, b, c in zip(prompts, budgets, classes)]
            outs0 = [list(map(int, r.get(timeout=600))) for r in reqs0]
            assert srv0.preemptions_total == 0 \
                and srv0.preempt_parked == 0
            assert srv0.class_depths() is None, \
                f"{GATE} off still reports class depths"
        finally:
            srv0.stop()
        if outs0 != want:
            raise AssertionError(
                f"priority-off storm is not FIFO bit-identical: "
                f"{outs0} vs {want}")
        if obs.enabled():
            grown = "\n".join(set(obs.render().splitlines())
                              - lines_before)
            for name in ("bigdl_llm_preemptions_total",
                         "bigdl_llm_queue_depth_class",
                         "bigdl_llm_preempt_parked"):
                assert name not in grown, \
                    f"{GATE} off grew metric series {name}"

        # warm the resume shapes: a second pass over every prompt hits
        # the radix chains the first pass indexed, compiling the
        # partial-prefill suffix programs preempt resumes re-enter
        # (the compiled-step cache is shared across engine instances)
        srv_w = LLMServer(model, max_batch=2, max_seq_len=64,
                          page_size=8, num_pages=num_pages,
                          kvcache=True).start()
        try:
            for p, b in zip(prompts, budgets):
                srv_w.submit(p, max_new_tokens=b).get(timeout=600)
                srv_w.submit(p, max_new_tokens=b).get(timeout=600)
        finally:
            srv_w.stop()

        # --- part 2: the FIFO reference storm (scheduler off) under
        # the same step-delay plan — the TTFT baseline the scheduler
        # must beat. llm.step delays stretch every decode pass so the
        # batch saturation genuinely blocks the burst.
        plan_off = rel.FaultPlan(seed=seed)
        plan_off.add("llm.step", "delay", times=None, delay=0.02)
        rel.set_plan(plan_off)
        try:
            outs_off, ttft_off, _ = storm(priority=False)
        finally:
            rel.set_plan(None)
        if outs_off != want:
            raise AssertionError(
                f"FIFO reference storm diverged: {outs_off} vs {want}")

        # --- part 3: the priority storm, scheduler on, flight recorder
        # on, seeded llm.preempt faults aborting preemption attempts
        # (the site fires before any state mutates, so an aborted
        # attempt must leave the victim decoding untouched and the
        # next engine pass retries the preemption)
        conf.set(FLIGHT_GATE, "true")
        r = flight.ring()
        evs = r.events() if r is not None else []
        t_before = {
            "preempt": sum(1 for e in evs if e["kind"] == "preempt"),
            "resume": sum(1 for e in evs
                          if e["kind"] == "preempt_resume"),
            "dropped": r.dropped if r is not None else 0,
        }
        c_before = _counter_total("bigdl_llm_preemptions_total")
        plan = rel.FaultPlan(seed=seed)
        plan.add("llm.preempt", "raise", times=1, after=0)
        plan.add("llm.preempt", "delay", times=None, delay=0.005)
        plan.add("llm.step", "delay", times=None, delay=0.02)
        rel.set_plan(plan)
        try:
            outs_on, ttft_on, srv = storm(priority=True)
        finally:
            rel.set_plan(None)
        if outs_on != want:
            raise AssertionError(
                f"priority storm diverged under preemption "
                f"(fired: {[f'{s}:{a}' for s, a in plan.fired]}): "
                f"{outs_on} vs {want}")
        if srv.preemptions_total == 0:
            raise AssertionError(
                "priority storm completed without a single preemption "
                "— the burst never displaced a batch decode")
        if not any(s == "llm.preempt" for s, _ in plan.fired):
            raise AssertionError(
                "priority storm armed but no llm.preempt fault fired")
        if srv.preempt_resumes_total != srv.preemptions_total:
            raise AssertionError(
                f"{srv.preemptions_total} preemptions but "
                f"{srv.preempt_resumes_total} resumes — a preempted "
                "request never re-admitted")
        # ledger/arena idle: every page charge returned at the drain,
        # every parked handoff blob consumed by its resume
        if srv._budget_avail != num_pages - 1:
            raise AssertionError(
                f"priority storm ledger leak: idle budget "
                f"{srv._budget_avail} vs pool {num_pages - 1}")
        if srv.preempt_parked != 0:
            raise AssertionError(
                f"{srv.preempt_parked} exported chains still parked "
                "after every request completed")
        if srv._tier is not None and srv._tier.migrator.inflight():
            raise AssertionError("arena migrations still in flight")
        # reconciliation: flight events == counter == plain-int ledger
        r = flight.ring()
        evs = r.events() if r is not None else []
        t_after = {
            "preempt": sum(1 for e in evs if e["kind"] == "preempt"),
            "resume": sum(1 for e in evs
                          if e["kind"] == "preempt_resume"),
            "dropped": r.dropped if r is not None else 0,
        }
        if t_after["dropped"] != t_before["dropped"]:
            raise AssertionError(
                "flight ring dropped events mid-check; raise "
                "bigdl.observability.flight.capacity")
        ev_preempt = t_after["preempt"] - t_before["preempt"]
        ev_resume = t_after["resume"] - t_before["resume"]
        if ev_preempt != srv.preemptions_total:
            raise AssertionError(
                f"{ev_preempt} flight preempt events vs "
                f"{srv.preemptions_total} ledger preemptions")
        if ev_resume != srv.preempt_resumes_total:
            raise AssertionError(
                f"{ev_resume} flight preempt_resume events vs "
                f"{srv.preempt_resumes_total} ledger resumes")
        counters_reconciled: object = "obs disabled: ledger-only"
        if c_before is not None:
            c_delta = _counter_total("bigdl_llm_preemptions_total") \
                - c_before
            if c_delta != srv.preemptions_total:
                raise AssertionError(
                    f"bigdl_llm_preemptions_total moved {c_delta} for "
                    f"{srv.preemptions_total} ledger preemptions")
            counters_reconciled = True
        # the headline: interactive TTFT measurably better than FIFO
        worst_on = max(ttft_on) if ttft_on else None
        worst_off = max(ttft_off) if ttft_off else None
        if worst_on is None or worst_off is None:
            raise AssertionError("a storm stamped no interactive TTFT")
        if worst_on >= worst_off:
            raise AssertionError(
                f"scheduler-on interactive TTFT {worst_on * 1e3:.1f}ms "
                f"is no better than FIFO {worst_off * 1e3:.1f}ms — "
                "preemption bought nothing")
        return {
            "seed": seed,
            "requests": len(prompts),
            "events_fired": [f"{s}:{a}" for s, a in plan.fired],
            "preemptions": srv.preemptions_total,
            "resumes": srv.preempt_resumes_total,
            "flight_events": {"preempt": ev_preempt,
                              "resume": ev_resume},
            "counters_reconciled": counters_reconciled,
            "idle_budget": srv._budget_avail,
            "parked": srv.preempt_parked,
            "interactive_ttft_on_ms": round(worst_on * 1e3, 3),
            "interactive_ttft_off_ms": round(worst_off * 1e3, 3),
            "lost_requests": 0,
            "match": True,
        }
    finally:
        if not was_enabled:
            rel.disable()
        if prev_flight is None:
            conf.unset(FLIGHT_GATE)
        else:
            conf.set(FLIGHT_GATE, prev_flight)
        if prev_sync is None:
            conf.unset("bigdl.llm.kvtier.sync")
        else:
            conf.set("bigdl.llm.kvtier.sync", prev_sync)


class ElasticUnsupported(RuntimeError):
    """This jax build cannot do loopback multi-process distributed
    init — the elastic pass is skipped, mirroring the graceful skip in
    tests/test_multihost.py."""


#: The elastic worker: an ordinary Engine.init + optimizer script
#: (everything elastic arrives via the launcher's env). The seeded kill
#: hard-exits 1-of-N processes mid-epoch in generation 0 only.
#:
#: Backend probe: loopback CPU jax.distributed can COORDINATE (the
#: membership/heartbeat/restart machinery is fully real) but cannot run
#: multi-process computations — in that case each process trains the
#: same LocalOptimizer trajectory on the full data, which preserves the
#: whole recovery contract (kill -> supervisor restart -> snapshot
#: resume -> bit-identical weights). On a TPU pod the probe passes and
#: the run takes the true DistriOptimizer shard_map path.
_ELASTIC_WORKER = textwrap.dedent("""
    import logging, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    logging.basicConfig(level=logging.INFO)   # resume lines -> the log

    from bigdl_tpu.utils.conf import conf
    from bigdl_tpu.utils.engine import Engine
    mesh = Engine.init()   # coordinator/nprocs/pid from the launcher env
    pid = jax.process_index()
    gen = conf.get_int("bigdl.elastic.generation", 0) or 0

    mode = "distri"
    try:   # can this backend actually COMPUTE across processes?
        from jax.sharding import NamedSharding, PartitionSpec as P
        jax.device_put(np.zeros(8, np.float32),
                       NamedSharding(mesh, P())).block_until_ready()
    except Exception as e:
        if "Multiprocess computations" not in str(e):
            raise
        mode = "local"
    print("MODE", mode, flush=True)

    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn.module import set_seed
    from bigdl_tpu.optim.optimizer import (BaseOptimizer,
                                           DistriOptimizer,
                                           LocalOptimizer)
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.trigger import Trigger

    # seeded chaos: slow every elastic-guarded step so heartbeats and
    # snapshot commits interleave with real step traffic
    delay = float(os.environ.get("ELASTIC_CHAOS_STEP_DELAY", "0") or 0)
    if delay:
        from bigdl_tpu import reliability as rel
        plan = rel.FaultPlan(seed=0)
        plan.add("elastic.step", "delay", times=None, delay=delay)
        rel.set_plan(plan)

    # the kill: "pid:step" — die HARD (no cleanup, no checkpoint) once
    # past that step, generation 0 only
    die = os.environ.get("ELASTIC_CHAOS_DIE", "")
    if die:
        dpid, dstep = (int(v) for v in die.split(":"))
        orig = BaseOptimizer._after_iteration

        def lethal(self, params, states, opt_state, state):
            if pid == dpid and gen == 0 and state["neval"] > dstep:
                print("CHAOS_KILLED", state["neval"], flush=True)
                os._exit(17)
            return orig(self, params, states, opt_state, state)

        BaseOptimizer._after_iteration = lethal

    set_seed(0)    # identical init on every process (ModelBroadcast)
    model = nn.Sequential().add(nn.Linear(10, 16)).add(nn.ReLU())\\
        .add(nn.Linear(16, 2)).add(nn.LogSoftMax())

    # 4 global batches of 64 rows per epoch
    nproc = jax.process_count()
    rs = np.random.RandomState(0)
    x_all = rs.rand(256, 10).astype(np.float32)
    y_all = ((x_all.sum(1) > 5).astype(np.int32) + 1)
    from bigdl_tpu.feature.dataset import LocalDataSet
    if mode == "distri":
        # each process holds its own interleaved slice of every batch
        # (device order = process order on the data axis); unshuffled:
        # exact resume requires a deterministic per-epoch batch order
        lb = 64 // nproc
        x = x_all.reshape(4, nproc, lb, 10)[:, pid].reshape(-1, 10)
        y = y_all.reshape(4, nproc, lb)[:, pid].reshape(-1)
        opt = DistriOptimizer(model, LocalDataSet(x, y, shuffle=False),
                              nn.ClassNLLCriterion(), batch_size=lb,
                              end_trigger=Trigger.max_epoch(3))
    else:
        # replicated local training: every process runs the identical
        # trajectory over the full data
        opt = LocalOptimizer(model,
                             LocalDataSet(x_all, y_all, shuffle=False),
                             nn.ClassNLLCriterion(), batch_size=64,
                             end_trigger=Trigger.max_epoch(3))
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_checkpoint(os.environ["ELASTIC_CHAOS_CKPT"],
                       Trigger.every_epoch())
    trained = opt.optimize()   # resume swaps opt.model: hash the result

    import hashlib
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(trained.parameters_dict()):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    print("WHASH", h.hexdigest(), flush=True)
""")


def _elastic_run(ckpt_dir: str, die: str = "", step_delay: float = 0.05,
                 timeout: float = 600.0):
    """One launcher-supervised worker-set run; returns (record,
    final-generation WHASH list, launcher)."""
    from bigdl_tpu.elastic.launch import ElasticJobFailed, ElasticLauncher

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.update({
        "ELASTIC_CHAOS_CKPT": ckpt_dir,
        "ELASTIC_CHAOS_STEP_DELAY": str(step_delay),
        # fast detection for the harness; production defaults are in conf
        "BIGDL_TPU_ELASTIC_HEARTBEAT_INTERVAL": "0.1",
        "BIGDL_TPU_ELASTIC_HEARTBEAT_TIMEOUT": "5.0",
        "BIGDL_TPU_ELASTIC_SNAPSHOT_EVERY": "2",
    })
    if die:
        env["ELASTIC_CHAOS_DIE"] = die
    else:
        env.pop("ELASTIC_CHAOS_DIE", None)

    launcher = ElasticLauncher([sys.executable, "-c", _ELASTIC_WORKER],
                               nprocs=2, max_restarts=2, env=env,
                               cwd=repo_root)
    try:
        record = launcher.run(timeout=timeout)
    except ElasticJobFailed as e:
        blob = " ".join(e.log_tails.values())
        if ("DISTRIBUTED" in blob.upper() or "coordinator" in blob.lower()
                or "UNAVAILABLE" in blob):
            raise ElasticUnsupported(
                f"loopback jax.distributed unsupported: {blob[-300:]}"
            ) from e
        raise
    gen = launcher.supervisor.generation
    hashes = []
    for pid in range(launcher.nprocs):
        path = os.path.join(record["log_dir"], f"worker-g{gen}-p{pid}.log")
        with open(path, errors="replace") as f:
            lines = [ln.split()[1] for ln in f
                     if ln.startswith("WHASH")]
        hashes.append(lines[-1] if lines else None)
    with open(os.path.join(record["log_dir"], "worker-g0-p0.log"),
              errors="replace") as f:
        modes = [ln.split()[1] for ln in f if ln.startswith("MODE")]
    record["mode"] = modes[-1] if modes else "unknown"
    return record, hashes, launcher


def run_elastic_chaos(seed: int = 0, die_after: int = 9,
                      smoke: bool = False) -> dict:
    """ISSUE 10 acceptance: a 2-process DistriOptimizer run loses one
    process mid-epoch; the supervisor restarts the worker set; the job
    finishes with final weights BIT-IDENTICAL to the clean run at the
    same world size (snapshot-based resume at the exact saved
    iteration). Also asserts the disabled-mode contract: with
    ``bigdl.elastic.enabled=false`` the optimizer builds no supervisor,
    no agent thread, no snapshot ring, and mints no ``bigdl_elastic_*``
    metric series. ``smoke`` currently only shortens the wall-clock
    budget (the run is already minimal: 3 epochs x 4 tiny steps)."""
    import threading

    from bigdl_tpu import observability as obs

    # --- disabled-mode structural absence (in-process, cheap)
    before = set(obs.render().splitlines()) if obs.enabled() else set()
    clean_disabled = _train_once(32, 1, 16, ckpt_dir=None)
    assert np.isfinite(clean_disabled)
    from bigdl_tpu.optim.optimizer import BaseOptimizer  # noqa: F401
    assert not [t for t in threading.enumerate()
                if t.name.startswith("bigdl-elastic")], \
        "elastic-disabled training started an elastic thread"
    if obs.enabled():
        grown = "\n".join(set(obs.render().splitlines()) - before)
        assert "bigdl_elastic_" not in grown, \
            f"disabled mode grew elastic series:\n{grown}"

    timeout = 420.0 if smoke else 600.0
    with tempfile.TemporaryDirectory() as d_clean, \
            tempfile.TemporaryDirectory() as d_kill:
        clean_rec, clean_hashes, _ = _elastic_run(
            os.path.join(d_clean, "ckpt"), die="", timeout=timeout)
        kill_rec, kill_hashes, kill_launcher = _elastic_run(
            os.path.join(d_kill, "ckpt"), die=f"1:{die_after}",
            timeout=timeout)

        # the kill actually fired, mid-epoch, and the set restarted
        g0p1 = os.path.join(kill_rec["log_dir"], "worker-g0-p1.log")
        with open(g0p1, errors="replace") as f:
            killed = [ln for ln in f if ln.startswith("CHAOS_KILLED")]
        resumed = []
        for pid in range(2):
            path = os.path.join(kill_rec["log_dir"],
                                f"worker-g1-p{pid}.log")
            if os.path.exists(path):
                with open(path, errors="replace") as f:
                    resumed += [ln for ln in f if "auto-resuming" in ln]
    out = {
        "seed": seed,
        "die_after": die_after,
        "mode": kill_rec["mode"],
        "clean": {k: clean_rec[k] for k in ("generations", "restarts")},
        "kill": {k: kill_rec[k] for k in ("generations", "restarts")},
        "kill_failures": kill_rec["failures"],
        "clean_hashes": clean_hashes,
        "kill_hashes": kill_hashes,
        "match": (clean_hashes[0] is not None
                  and len(set(clean_hashes + kill_hashes)) == 1),
    }
    if not killed:
        raise AssertionError(
            "elastic chaos armed but process 1 never died — the kill "
            f"step {die_after} landed outside the run")
    if kill_rec["restarts"] < 1:
        raise AssertionError(
            "elastic chaos lost a process but the supervisor never "
            f"restarted the worker set: {kill_rec}")
    if not resumed:
        raise AssertionError(
            "generation 1 never auto-resumed from the snapshot tier — "
            "recovery restarted training from scratch")
    if clean_rec["restarts"] != 0:
        raise AssertionError(
            f"the clean elastic run restarted: {clean_rec}")
    if not out["match"]:
        raise AssertionError(
            f"elastic chaos divergence: clean {clean_hashes} vs "
            f"recovered {kill_hashes} — recovery replayed or dropped "
            "work")
    # a passing run does not leak worker-log dirs into /tmp across
    # repeated chaos/bench/test invocations; failures above keep them
    # for diagnostics
    import shutil
    for rec in (clean_rec, kill_rec):
        shutil.rmtree(rec["log_dir"], ignore_errors=True)
    return out


def run_all_chaos(seed: int = 0) -> dict:
    """Every chaos suite, one record per pass (the ``chaos_all``
    telemetry block in ``bench.py``). Each pass asserts its own
    parity contract; a failing pass lands as an ``error`` entry
    instead of killing the others.

    ISSUE 11: the whole run executes under the ``bigdl.analysis.
    lockwatch`` runtime witness — every lock the suites construct is
    order-checked against the process-global table, and ANY observed
    inversion fails the run (``ok: false`` + the violating pair in the
    ``lockwatch`` block). The knob is restored afterwards so the
    process leaves the way it came."""
    from bigdl_tpu.analysis import lockwatch
    from bigdl_tpu.utils.conf import conf

    # restore-exactly bookkeeping: remember whether the SET LAYER had
    # an explicit value (conf.get would return the baked-in default and
    # re-setting that would shadow the env/file layers forever), and
    # whether a caller already installed the witness (then its edge
    # table and installation are theirs — don't reset or uninstall)
    with conf._lock:
        prev = conf._set_layer.get("bigdl.analysis.lockwatch")
    was_installed = lockwatch.installed()
    conf.set("bigdl.analysis.lockwatch", "true")
    if not was_installed:
        lockwatch.reset()
    installed = lockwatch.maybe_install() or was_installed
    out = {}
    try:
        for name, fn in (("train", lambda: run_chaos(seed=seed, events=3,
                                                     smoke=True)),
                         ("kvcache", lambda: run_kvcache_chaos(seed=seed)),
                         ("kvtier", lambda: run_kvtier_chaos(seed=seed)),
                         ("mixed", lambda: run_mixed_chaos(seed=seed)),
                         ("spec", lambda: run_spec_chaos(seed=seed)),
                         ("failover", lambda: run_failover_chaos(
                             seed=seed, smoke=True)),
                         ("flight", lambda: run_flight_chaos(
                             seed=seed, smoke=True)),
                         ("fleet", lambda: run_fleet_chaos(
                             seed=seed, smoke=True)),
                         ("preempt", lambda: run_preempt_chaos(
                             seed=seed, smoke=True)),
                         ("elastic", lambda: run_elastic_chaos(
                             seed=seed, smoke=True)),
                         ("alerts", lambda: run_alerts_chaos(
                             seed=seed, smoke=True)),
                         ("api", lambda: run_api_chaos(
                             seed=seed, smoke=True))):
            try:
                out[name] = fn()
            except ElasticUnsupported as e:
                out[name] = {"skipped": repr(e)}  # no loopback distributed
            except Exception as e:  # noqa: BLE001 — one bad suite
                out[name] = {"error": repr(e)}  # must not hide the rest
    finally:
        violations = lockwatch.violations()
        out["lockwatch"] = {"installed": installed,
                            "edges_observed": len(
                                lockwatch.observed_edges()),
                            "violations": violations}
        if installed and not was_installed:
            lockwatch.uninstall()
        if prev is None:
            conf.unset("bigdl.analysis.lockwatch")
        else:
            conf.set("bigdl.analysis.lockwatch", prev)
    out["ok"] = all("error" not in v for v in out.values()
                    if isinstance(v, dict)) and not violations
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events", type=int, default=5)
    ap.add_argument("--full", action="store_true",
                    help="bigger model/data than the smoke default")
    ap.add_argument("--kvcache", action="store_true",
                    help="run the kvcache.evict eviction-race pass "
                         "instead of the training chaos run (ISSUE 5)")
    ap.add_argument("--kvtier", action="store_true",
                    help="run the host-tier migration-fault pass: "
                         "delayed/failed spills and fetches must keep "
                         "greedy outputs identical (ISSUE 6)")
    ap.add_argument("--mixed", action="store_true",
                    help="run the chunked-admission fault pass: a "
                         "seeded llm.chunk raise mid-chain must free "
                         "the partial chain's pages/budget, fail the "
                         "request retriably, and a resubmission must "
                         "be greedy-identical to the clean run "
                         "(ISSUE 14)")
    ap.add_argument("--failover", action="store_true",
                    help="run the router kill-storm pass: mid-stream "
                         "decode-worker kills and watchdog-tripping "
                         "engine stalls must lose zero requests with "
                         "greedy outputs bit-identical (ISSUE 7)")
    ap.add_argument("--flight", action="store_true",
                    help="run the flight-recorder reconciliation pass: "
                         "a kill storm + pool-pressure replay with the "
                         "recorder on — shed/failover/eviction decision "
                         "events must reconcile EXACTLY with the "
                         "bigdl_*_total counters, and disabled mode "
                         "(bigdl.observability.flight.enabled off) "
                         "must be structurally absent (ISSUE 16)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the elastic-fleet soak: load spike -> "
                         "scale-out -> worker killed mid-drain -> "
                         "scale-in, with zero lost requests, greedy "
                         "outputs bit-identical to a clean run, and "
                         "drained workers' warm KV chains serving "
                         "prefix hits on survivors (ISSUE 15)")
    ap.add_argument("--preempt", action="store_true",
                    help="run the priority-storm pass: an interactive "
                         "burst over saturated batch-class decodes "
                         "with seeded llm.preempt faults — every "
                         "preempted request completes bit-identical, "
                         "zero lost, flight events/counters/ledgers "
                         "reconcile exactly, and interactive TTFT "
                         "beats the scheduler-off baseline (ISSUE 17)")
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic-training pass: a seeded kill "
                         "of 1-of-2 DistriOptimizer processes mid-"
                         "epoch must recover via the supervisor with "
                         "final weights bit-identical to the clean "
                         "run (ISSUE 10)")
    ap.add_argument("--spec", action="store_true",
                    help="run the self-speculative fault pass: seeded "
                         "llm.spec raises/delays mid-verify must degrade "
                         "to plain decode with greedy outputs "
                         "bit-identical to the clean run, zero page-"
                         "budget leak, and draft/verify flight events "
                         "reconciling exactly with the engine ledgers "
                         "and bigdl_llm_spec_* counters (ISSUE 19)")
    ap.add_argument("--alerts", action="store_true",
                    help="run the time-series/alerting pass: a seeded "
                         "failover storm must flip the fast-burn SLO "
                         "alert to firing within one evaluation "
                         "interval and resolve after recovery, with "
                         "transitions reconciling exactly against "
                         "flight alert_fire/alert_resolve events, the "
                         "autoscaler making identical decisions "
                         "through the store primitive, and disabled "
                         "mode structurally absent (ISSUE 18)")
    ap.add_argument("--api", action="store_true",
                    help="run the OpenAI gateway pass: a mid-stream "
                         "router.dispatch kill under a live SSE client "
                         "must keep the concatenated stream "
                         "bit-identical to model.generate with every "
                         "relayed token SLO-stamped exactly once, and "
                         "disabled mode must 404 naming "
                         "bigdl.llm.api.enabled with zero bigdl_api_* "
                         "series (ISSUE 20)")
    ap.add_argument("--all", action="store_true",
                    help="run every chaos suite (train, kvcache, "
                         "kvtier, mixed, failover, fleet, preempt, "
                         "spec, elastic, alerts, api) and report one "
                         "record per pass (the bench.py chaos_all "
                         "block)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (same as "
                         "JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    if args.all:
        out = run_all_chaos(seed=args.seed)
        print(json.dumps(out, indent=1))
        if not out["ok"]:
            sys.exit(1)
        return
    if args.api:
        out = run_api_chaos(seed=args.seed)
    elif args.spec:
        out = run_spec_chaos(seed=args.seed)
    elif args.elastic:
        out = run_elastic_chaos(seed=args.seed)
    elif args.alerts:
        out = run_alerts_chaos(seed=args.seed)
    elif args.preempt:
        out = run_preempt_chaos(seed=args.seed)
    elif args.flight:
        out = run_flight_chaos(seed=args.seed)
    elif args.fleet:
        out = run_fleet_chaos(seed=args.seed)
    elif args.mixed:
        out = run_mixed_chaos(seed=args.seed)
    elif args.failover:
        out = run_failover_chaos(seed=args.seed)
    elif args.kvtier:
        out = run_kvtier_chaos(seed=args.seed)
    elif args.kvcache:
        out = run_kvcache_chaos(seed=args.seed)
    else:
        out = run_chaos(seed=args.seed, events=args.events,
                        smoke=not args.full)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
