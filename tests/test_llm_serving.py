"""Continuous-batching LLM serving worker (ref: P:llm/serving — the
fastchat worker / vLLM integration row of SURVEY.md §2.8)."""

import time

import numpy as np
import pytest

from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=64)


class TestLLMServer:
    def test_single_request_matches_generate(self, model):
        """A served request must produce exactly the model's own greedy
        continuation."""
        ids = np.array([3, 1, 4, 1, 5], np.int32)
        want = model.generate(ids[None], max_new_tokens=6)[0, 5:]
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        try:
            req = srv.submit(ids, max_new_tokens=6)
            got = req.get(timeout=120)
        finally:
            srv.stop()
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_continuous_batching_concurrent_requests(self, model):
        """Several overlapping requests of different lengths share the
        batch; each result equals its solo greedy continuation."""
        prompts = [np.array(p, np.int32) for p in
                   ([1, 2, 3], [7, 8], [9, 10, 11, 12], [5], [6, 4])]
        lens = [5, 3, 4, 6, 2]
        want = [model.generate(p[None], max_new_tokens=n)[0, len(p):]
                for p, n in zip(prompts, lens)]
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, lens)]
            got = [r.get(timeout=300) for r in reqs]
        finally:
            srv.stop()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
        # with max_batch=2 and 5 requests, slots must have been reused
        assert srv.steps >= max(lens)

    def test_paged_16_mixed_length_requests(self, model):
        """The paged-cache north star (VERDICT r3 missing #1): 16
        concurrent mixed-length requests through 4 batch slots, each
        matching its solo greedy continuation, with KV HBM proportional
        to tokens in flight (pages, not slots × max_seq_len)."""
        rs = np.random.RandomState(7)
        prompts = [np.asarray(rs.randint(0, 250, rs.randint(1, 20)),
                              np.int32) for _ in range(16)]
        lens = [int(rs.randint(1, 10)) for _ in range(16)]
        want = [model.generate(p[None], max_new_tokens=n)[0, len(p):]
                for p, n in zip(prompts, lens)]
        srv = LLMServer(model, max_batch=4, max_seq_len=32,
                        page_size=16).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, lens)]
            got = [r.get(timeout=600) for r in reqs]
        finally:
            srv.stop()
        for j, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(np.asarray(g), w,
                                          err_msg=f"request {j}")
        # all requests done -> every page returned to the pool
        assert srv.pages_in_use == 0
        assert srv._budget_avail == srv._num_pages - 1
        assert sorted(srv._free) == list(range(1, srv._num_pages))

    def test_paged_budget_admission_small_pool(self, model):
        """A pool smaller than max_batch × worst case still serves every
        request: admission reserves page budgets and queues the rest."""
        prompts = [np.arange(1, 9, dtype=np.int32) for _ in range(6)]
        want = [model.generate(p[None], max_new_tokens=8)[0, len(p):]
                for p in prompts]
        # each request needs ceil(16/16) = 1..2 pages; pool of 4 usable
        srv = LLMServer(model, max_batch=4, max_seq_len=32,
                        page_size=16, num_pages=5).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
            got = [r.get(timeout=600) for r in reqs]
        finally:
            srv.stop()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)

    @pytest.mark.parametrize("depth", [1, 4])
    def test_greedy_parity_under_concurrent_jax_load(self, model, depth):
        """Regression for the round-3 flaky race: concurrent jax
        executions on OTHER threads let the async CPU runtime recycle
        the engine's just-dropped cache buffers while the step consuming
        them was still in flight (14/30 greedy-parity mismatches before
        the block_until_ready barrier after prefill and decode scatters;
        0/30 after). Hammer threads + randomized submit timing. Re-run
        under pipelining (ISSUE 4): depth 4 replaces the per-step
        barrier with fence-pinned in-flight records, which must hold the
        same buffer-lifetime guarantee under the same load."""
        import threading
        import time

        import jax
        import jax.numpy as jnp

        ids = np.array([3, 1, 4, 1, 5], np.int32)
        want = model.generate(ids[None], max_new_tokens=6)[0, 5:]
        stop = threading.Event()

        def hammer():
            # input changes every call: some runtimes memoize identical
            # (program, args) executions, which would make a fixed-input
            # hammer generate zero real concurrent device traffic
            a = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
            f = jax.jit(lambda x: jnp.tanh(x @ x) + 1e-6)
            while not stop.is_set():
                a = f(a).block_until_ready()

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for it in range(6):
                srv = LLMServer(model, max_batch=2, max_seq_len=32,
                                pipeline_depth=depth).start()
                try:
                    time.sleep((it % 4) * 0.001)
                    req = srv.submit(ids, max_new_tokens=6)
                    got = np.asarray(req.get(timeout=120))
                finally:
                    srv.stop()
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"iteration {it}")
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)


class TestPipelinedEngine:
    """ISSUE 4: the async dispatch window must change THROUGHPUT, never
    tokens — greedy parity vs generate() at every depth, strict
    synchrony at depth 1, and budget/page invariants under speculative
    dispatch past data-dependent request ends."""

    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_greedy_parity_across_depths(self, model, depth):
        """Mixed-length overlapping requests through 2 slots at each
        pipeline depth: slot churn forces speculative steps for
        finished requests (their tokens must be discarded) and
        re-prefill into slots with steps still in flight."""
        prompts = [np.array(p, np.int32) for p in
                   ([1, 2, 3], [7, 8], [9, 10, 11, 12], [5], [6, 4])]
        lens = [5, 3, 4, 6, 2]
        want = [model.generate(p[None], max_new_tokens=n)[0, len(p):]
                for p, n in zip(prompts, lens)]
        srv = LLMServer(model, max_batch=2, max_seq_len=32,
                        pipeline_depth=depth).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, lens)]
            got = [r.get(timeout=300) for r in reqs]
        finally:
            srv.stop()
        for j, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(np.asarray(g), w,
                                          err_msg=f"request {j}")
        # every page returned despite speculative in-flight steps
        assert srv.pages_in_use == 0
        assert srv._budget_avail == srv._num_pages - 1
        assert sorted(srv._free) == list(range(1, srv._num_pages))
        assert not srv._inflight and not srv._pending_release

    def test_depth1_is_synchronous(self, model):
        """The acceptance contract: pipeline_depth=1 reproduces the
        synchronous engine — after every engine pass the in-flight
        window is empty and no pinned buffers survive, and with
        observability off no metric series exist at all."""
        from bigdl_tpu import observability as obs

        ids = np.array([3, 1, 4, 1, 5], np.int32)
        want = model.generate(ids[None], max_new_tokens=6)[0, 5:]
        obs.disable()
        try:
            before = len(obs.REGISTRY.collect())
            srv = LLMServer(model, max_batch=2, max_seq_len=32,
                            pipeline_depth=1)
            # drive the engine inline (no thread): inspect after passes
            req = srv.submit(ids, max_new_tokens=6)
            while not req.done.is_set():
                srv._admit()
                srv._step()
                assert len(srv._inflight) == 0      # drained every pass
                assert srv._pending_release == []   # nothing outlives it
            assert len(obs.REGISTRY.collect()) == before
        finally:
            obs.enable()
        np.testing.assert_array_equal(np.asarray(req.tokens), want)

    def test_small_pool_speculation_stays_inside_budget(self, model):
        """Speculative dispatch past a request's end must never allocate
        pages beyond the admission reserve: a pool barely larger than
        one request's worst case, deep pipeline, queued waiters — runs
        to completion (a budget overrun would IndexError the free list
        or deadlock admission) with exact greedy output."""
        prompts = [np.arange(1, 9, dtype=np.int32) for _ in range(6)]
        want = [model.generate(p[None], max_new_tokens=8)[0, len(p):]
                for p in prompts]
        srv = LLMServer(model, max_batch=4, max_seq_len=32,
                        page_size=16, num_pages=5,
                        pipeline_depth=4).start()
        try:
            reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
            got = [r.get(timeout=600) for r in reqs]
        finally:
            srv.stop()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
        assert srv._budget_avail == srv._num_pages - 1
        assert sorted(srv._free) == list(range(1, srv._num_pages))

    def test_pipeline_metrics_split(self, model):
        """The ISSUE 4 satellite's timing fix: decode time is reported
        as a host-scheduling slice and a fence-stall slice (plus the
        in-flight gauge), not one wall number hiding the barrier."""
        from bigdl_tpu import observability as obs

        srv = LLMServer(model, max_batch=2, max_seq_len=32,
                        pipeline_depth=2).start()
        try:
            srv.submit(np.array([3, 1, 4], np.int32),
                       max_new_tokens=5).get(timeout=120)
        finally:
            srv.stop()
        text = obs.render()
        assert "bigdl_llm_decode_host_seconds" in text
        assert "bigdl_llm_decode_stall_seconds" in text
        assert "bigdl_llm_pipeline_inflight" in text
        # the always-on accounting the microbench reads
        assert srv.host_seconds > 0.0
        assert srv.stall_seconds >= 0.0


class TestDecodeMicrobench:
    @pytest.mark.perf
    def test_microbench_runs_and_reports_split(self, model):
        """tools/microbench_decode.py end-to-end on the tiny model: one
        record per depth with the step/host/stall numbers bench.py's
        telemetry block embeds (values advisory — shared hosts)."""
        from tools.microbench_decode import run_microbench

        out = run_microbench(depths=(1, 2), batch=2, tokens=6,
                             warmup_tokens=2, model=model)
        for k in ("depth1", "depth2"):
            assert out[k]["steps"] > 0
            assert out[k]["step_ms"] > 0
            assert out[k]["host_ms_per_step"] >= 0
            assert out[k]["stall_ms_per_step"] >= 0
        assert "speedup_vs_depth1" in out


# ---------------------------------------------------------------------------
# the engine pass's own spans and stamps (ISSUE 25)
# ---------------------------------------------------------------------------

PHASES = ("llm/admit", "llm/grant", "llm/dispatch", "llm/fence_wait",
          "llm/drain")
# what tiles a whole-prompt prefill inside its ``llm/prefill`` (ISSUE 35)
CHILDREN = ("llm/prefill_stage", "llm/prefill_dispatch",
            "llm/prefill_finish")
_EPS = 1e-7     # perf_counter arithmetic through float microseconds

_ENGINES = {
    "paged": dict(),
    "mixed": dict(page_size=8, mixed=True, chunk_tokens=8),
    "spec": dict(page_size=8, spec=True, spec_k=8),
}


def _end(rec):
    return rec["t0"] + rec["dur"] / 1e6


def _serve_traced(model, kind):
    """Serve a few requests, none with a trace context, on a fresh
    engine of ``kind``; returns the engine, the requests and the ring
    records of its thread in start order."""
    from bigdl_tpu import observability as obs

    rs = np.random.RandomState(42)
    pattern = rs.randint(0, 250, 5).astype(np.int32)
    prompts = [np.tile(pattern, 6), rs.randint(0, 250, 7).astype(np.int32),
               np.array([3, 1, 4], np.int32)]
    lens = [24 if kind == "spec" else 6, 6, 4]
    srv = LLMServer(model, max_batch=2, max_seq_len=64,
                    **_ENGINES[kind]).start()
    try:
        # first use compiles: keep that out of the ring under test
        srv.submit(prompts[0], max_new_tokens=2).get(timeout=600)
        while not srv.engine_idle():
            time.sleep(0.001)
        time.sleep(0.02)    # the pass that went idle records itself
        obs.TRACE.clear()
        reqs = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        for r in reqs:
            r.get(timeout=600)
        tid = srv._thread.ident
    finally:
        srv.stop()
    recs = sorted((r for r in obs.TRACE.spans() if r["tid"] == tid),
                  key=lambda r: r["t0"])
    return srv, reqs, recs


class TestPassSpans:
    @pytest.fixture(scope="class")
    def served(self, model):
        return _serve_traced(model, "paged")

    @pytest.mark.parametrize("kind", sorted(_ENGINES))
    def test_phases_tile_their_pass(self, model, served, kind):
        """Every dispatch path records the same bracket: the phases of
        one loop iteration follow one another without overlap, and
        ``llm/pass`` runs from the first one's start to the last one's
        end."""
        srv, _, recs = served if kind == "paged" \
            else _serve_traced(model, kind)
        passes = [r for r in recs if r["name"] == "llm/pass"]
        phases = [r for r in recs if r["name"] in PHASES]
        assert passes and phases
        owned = 0
        for p in passes:
            mine = [r for r in phases
                    if p["t0"] - _EPS <= r["t0"] <= _end(p) + _EPS]
            assert mine, p
            owned += len(mine)
            assert abs(mine[0]["t0"] - p["t0"]) <= _EPS
            assert abs(_end(mine[-1]) - _end(p)) <= _EPS
            for a, b in zip(mine, mine[1:]):
                assert _end(a) <= b["t0"] + _EPS, (a, b)
            names = [r["name"] for r in mine]
            assert names.count("llm/admit") <= 1
            if "llm/admit" in names:
                # ahead of a sweep only the drain of what was in flight
                # (ISSUE 31: deliver before admitting)
                assert set(names[:names.index("llm/admit")]) <= {
                    "llm/fence_wait", "llm/drain"}
        assert owned == len(phases)     # no phase outside a pass
        fns = {p["args"]["fn"] for p in passes} - {None}
        want = {"paged": "llm/decode_paged", "mixed": "llm/step_mixed",
                "spec": "llm/step_spec"}[kind]
        assert want in fns
        # a dispatched step has its llm/dispatch and its grant
        n_disp = sum(r["name"] == "llm/dispatch" for r in phases)
        n_solo = sum(r["name"] == "llm/dispatch"
                     and r["args"]["rows"] == 0 for r in phases)
        assert n_disp - n_solo == sum(
            p["args"]["fn"] is not None for p in passes if p["args"]["rows"])
        assert sum(r["name"] == "llm/grant" for r in phases) >= n_disp
        assert passes[-1]["args"]["step"] == srv.steps

    def test_tokens_in_flight_are_delivered_before_a_prefill(self, model):
        """A sweep that seats a request while a row decodes finds
        nothing in flight: the step the pass before dispatched is
        drained ahead of ``llm/admit`` (its ``llm/fence_wait`` and
        ``llm/drain`` open the pass), so a prefill's staging delays no
        finished token, and the staging and the prefill fall into one
        gap of the live row, not two (ISSUE 31)."""
        from bigdl_tpu import observability as obs

        srv = LLMServer(model, max_batch=2, max_seq_len=64).start()
        try:
            first = srv.submit(np.array([3, 1, 4, 1, 5], np.int32),
                               max_new_tokens=40)
            while len(first.tokens) < 4:
                time.sleep(0.001)
            obs.TRACE.clear()
            srv.submit(np.array([2, 7, 1, 8], np.int32),
                       max_new_tokens=4).get(timeout=600)
            first.get(timeout=600)
            tid = srv._thread.ident
        finally:
            srv.stop()
        recs = sorted((r for r in obs.TRACE.spans() if r["tid"] == tid),
                      key=lambda r: r["t0"])
        seated = [p for p in recs if p["name"] == "llm/pass"
                  and p["args"]["prefills"]]
        assert len(seated) == 1
        p = seated[0]
        mine = [r["name"] for r in recs if r["name"] in PHASES
                and p["t0"] - _EPS <= r["t0"] <= _end(p) + _EPS]
        assert mine[:3] == ["llm/fence_wait", "llm/drain", "llm/admit"]
        # and nothing else is drained by that pass: the step it
        # dispatches after the prefill is the only one in flight
        assert mine.count("llm/drain") == 1

    def test_fence_wait_brackets_only_the_fetch(self, served):
        """The fence stamp is read between the end of ``llm/fence_wait``
        and the start of ``llm/drain``: nothing but the fetch is in the
        one, everything after it in the other."""
        srv, reqs, recs = served
        waits = [r for r in recs if r["name"] == "llm/fence_wait"]
        drains = [r for r in recs if r["name"] == "llm/drain"]
        assert len(waits) == len(drains) > 0
        stamps = sorted({t for r in reqs for t in r.t_tokens})
        slots = [(_end(w), d["t0"]) for w, d in zip(waits, drains)]
        for t in stamps:
            assert any(lo - _EPS <= t <= hi + _EPS for lo, hi in slots), t
        assert all(not w["args"] for w in waits)
        # (``llm/queue_wait`` starts at the request's own submit stamp,
        # which may fall anywhere: into the wait ahead of its sweep too)
        nested = [r for r in recs for w in waits
                  if r is not w
                  and r["name"] not in ("llm/pass", "llm/queue_wait")
                  and w["t0"] <= r["t0"] < _end(w)]
        assert nested == []
        assert sum(w["dur"] for w in waits) / 1e6 <= srv.stall_seconds

    def test_stamps_are_taken_with_slo_off(self, served):
        srv, reqs, _ = served
        assert srv._slo is None
        for r in reqs:
            assert len(r.t_tokens) == len(r.tokens) > 0
            assert all(a <= b for a, b in zip(r.t_tokens, r.t_tokens[1:]))
            assert r.t_submit <= r.t_admit <= r.t_tokens[0] \
                <= r.t_first_token

    def test_queue_wait_and_admission_args(self, served):
        """``llm/queue_wait`` for requests that carry no trace context,
        on the request's own submit stamp; the admit and drain phases
        account for every request and every token."""
        _, reqs, recs = served
        by = {}
        for r in recs:
            by.setdefault(r["name"], []).append(r)
        waits = {r["args"]["request"]: r for r in by["llm/queue_wait"]}
        for r in reqs:
            w = waits[r.id]
            assert "trace" not in w["args"]
            assert w["t0"] == r.t_submit
            assert abs(_end(w) - r.t_admit) <= _EPS
        admits = by["llm/admit"]
        assert sum(a["args"]["admitted"] for a in admits) == len(reqs)
        assert sum(a["args"]["prefills"] for a in admits) == len(reqs)
        real = sum(a["args"]["prompt_tokens"] for a in admits)
        assert real == sum(len(r.prompt_ids) for r in reqs)
        assert sum(a["args"]["bucket_tokens"] for a in admits) >= real
        for pf in by["llm/prefill"]:
            assert pf["args"]["parent"] == "llm/admit"
            assert any(a["t0"] <= pf["t0"] and _end(pf) <= _end(a) + _EPS
                       for a in admits)
        assert sum(p["args"]["admitted"] for p in by["llm/pass"]) \
            == len(reqs)
        served_ids = [i for d in by["llm/drain"]
                      for i in d["args"]["requests"]]
        for r in reqs:
            assert served_ids.count(r.id) == len(r.tokens)
        assert sum(d["args"]["finished"] for d in by["llm/drain"]) \
            == len(reqs)

    def test_disabled_ring_stays_empty_stamps_still_taken(self, model):
        from bigdl_tpu import observability as obs
        obs.disable()
        try:
            obs.TRACE.clear()
            srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
            try:
                req = srv.submit(np.array([3, 1, 4], np.int32),
                                 max_new_tokens=5)
                req.get(timeout=120)
            finally:
                srv.stop()
            assert len(obs.TRACE) == 0
            assert len(req.t_tokens) == 5
            assert req.t_submit <= req.t_admit <= req.t_tokens[0]
        finally:
            obs.enable()

    def test_phases_reach_a_captured_jax_profile(self, model, tmp_path):
        """No switch: whoever captures a JAX profile of a serving
        process finds the five phases and a prefill's three children
        (ISSUE 35) on the engine thread's line, and not the enclosing
        ``llm/pass`` (it would be the longest host event over every
        device-idle gap)."""
        import glob

        import jax
        from jax.profiler import ProfileData
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        try:
            ids = np.array([3, 1, 4], np.int32)
            srv.submit(ids, max_new_tokens=2).get(timeout=120)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                srv.submit(ids, max_new_tokens=4).get(timeout=120)
            finally:
                jax.profiler.stop_trace()
        finally:
            srv.stop()
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[-1]
        lines = []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                names = {ev.name for ev in line.events
                         if ev.name.startswith("llm/")}
                if names:
                    lines.append(names)
        assert lines == [set(PHASES) | set(CHILDREN)]

    def test_span_names_pass_the_registry_gate(self):
        """The new names are registered and emitted, the span this PR
        took out is gone from both sides."""
        import os

        from bigdl_tpu.analysis import ProjectIndex, registries
        from bigdl_tpu.analysis import registrydrift
        names = set(PHASES) | {"llm/pass", "llm/queue_wait"}
        assert names <= set(registries.SPAN_NAMES)
        assert "llm/decode_step" not in registries.SPAN_NAMES
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        emitted = registrydrift.collect_literals(
            ProjectIndex.scan(root, ("bigdl_tpu",))).span
        assert names <= set(emitted)
        assert "llm/decode_step" not in emitted


# ---------------------------------------------------------------------------
# an admission and a late token gap, named from inside (ISSUE 35)
# ---------------------------------------------------------------------------

def _by_name(recs):
    by = {}
    for r in recs:
        by.setdefault(r["name"], []).append(r)
    return by


class TestAdmissionSpans:
    @pytest.fixture(scope="class")
    def served(self, model):
        return _serve_traced(model, "paged")

    def test_three_children_tile_a_prefill(self, served):
        """Stage, dispatch, finish: inside their ``llm/prefill``, in
        that order, without overlap; the dispatch holds the jit call
        and nothing else that records."""
        srv, reqs, recs = served
        by = _by_name(recs)
        assert len(by["llm/prefill"]) == len(reqs)
        for pf in by["llm/prefill"]:
            mine = [r for r in recs if r["name"] in CHILDREN
                    and pf["t0"] <= r["t0"] and _end(r) <= _end(pf) + _EPS]
            assert tuple(r["name"] for r in mine) == CHILDREN
            stage, disp, fin = mine
            assert _end(stage) <= disp["t0"] + _EPS
            assert _end(disp) <= fin["t0"] + _EPS
            rid = pf["args"]["request"]
            assert {r["args"]["request"] for r in mine} == {rid}
            assert {r["args"]["parent"] for r in mine} == {"llm/prefill"}
            tokens = pf["args"]["tokens"]
            assert stage["args"]["bucket"] == disp["args"]["bucket"] \
                == max(16, 1 << (tokens - 1).bit_length())
            # six operands and the fork's two, one class, no state
            assert stage["args"]["transfers"] == 8
            assert disp["args"]["fn"] == "llm/prefill_ragged"
            # ``_last``, the block table's row, the length
            assert fin["args"]["updates"] == 3
            inside = [r["name"] for r in recs if r is not disp
                      and disp["t0"] <= r["t0"] < _end(disp)]
            assert set(inside) <= {"xla/compile"}, inside
        # every dispatch is one prefill the gap counters know of (the
        # warm-up's was recorded before the ring was cleared)
        assert srv._prefill_seq == len(by["llm/prefill_dispatch"]) + 1

    def test_a_chunked_admission_records_its_finish_only(self, model):
        """The final chunk reaches ``_finish_prefill`` from the chunk
        path: ``llm/prefill_finish`` for every request, the other two
        for whole-prompt prefills alone."""
        _, reqs, recs = _serve_traced(model, "mixed")
        by = _by_name(recs)
        whole = len(by["llm/prefill"])
        assert 0 < whole < len(reqs)
        assert len(by["llm/prefill_stage"]) == whole
        assert len(by["llm/prefill_dispatch"]) == whole
        fins = by["llm/prefill_finish"]
        assert sorted(f["args"]["request"] for f in fins) \
            == sorted(r.id for r in reqs)
        assert sum("parent" not in f["args"]
                   or f["args"]["parent"] != "llm/prefill"
                   for f in fins) == len(reqs) - whole

    def test_every_gap_is_counted_once(self, served):
        """Σ ``gaps`` over the drains = Σ (tokens - 1) over the
        requests, in the ring and in the always-on counters."""
        srv, reqs, recs = served
        drains = _by_name(recs)["llm/drain"]
        want = sum(len(r.tokens) - 1 for r in reqs)
        assert sum(d["args"]["gaps"] for d in drains) == want
        assert srv.token_gaps_total == want + 1     # the warm-up's one
        for d in drains:
            assert 0 <= d["args"]["gaps_behind_prefill"] \
                <= d["args"]["gaps"] <= len(d["args"]["requests"])
        assert sum(d["args"]["gaps_behind_prefill"] for d in drains) \
            == srv.token_gaps_behind_prefill_total

    def test_a_gap_behind_a_prefill_is_the_live_rows(self, model):
        """One row decodes, a second request is seated: the live row's
        next token closes the one gap behind a prefill (the scenario of
        ``test_tokens_in_flight_are_delivered_before_a_prefill``); the
        newcomer's own gaps, and a lone request's, hold none."""
        from bigdl_tpu import observability as obs

        srv = LLMServer(model, max_batch=2, max_seq_len=64).start()
        try:
            lone = srv.submit(np.array([3, 1, 4], np.int32),
                              max_new_tokens=6)
            lone.get(timeout=600)
            assert srv.token_gaps_total == 5
            assert srv.token_gaps_behind_prefill_total == 0
            first = srv.submit(np.array([3, 1, 4, 1, 5], np.int32),
                               max_new_tokens=40)
            while len(first.tokens) < 4:
                time.sleep(0.001)
            obs.TRACE.clear()
            second = srv.submit(np.array([2, 7, 1, 8], np.int32),
                                max_new_tokens=4)
            second.get(timeout=600)
            first.get(timeout=600)
            tid = srv._thread.ident
        finally:
            srv.stop()
        assert srv.token_gaps_total == 5 + 39 + 3
        assert srv.token_gaps_behind_prefill_total == 1
        behind = [r for r in obs.TRACE.spans() if r["tid"] == tid
                  and r["name"] == "llm/drain"
                  and r["args"]["gaps_behind_prefill"]]
        assert len(behind) == 1
        # that drain also hands the newcomer its first token: no gap
        assert sorted(behind[0]["args"]["requests"]) \
            == sorted([first.id, second.id])
        assert behind[0]["args"]["gaps"] == 1
        assert behind[0]["args"]["gaps_behind_prefill"] == 1

    @pytest.mark.parametrize("kind", ["paged", "mixed"])
    def test_eager_time_is_on_every_grant_and_drain(self, model, served,
                                                    kind):
        """``eager_us``: the time inside the phase's eager device
        updates, 0 when none ran: a grant that took no page, a drain
        that freed no slot."""
        _, _, recs = served if kind == "paged" \
            else _serve_traced(model, kind)
        by = _by_name(recs)
        for r in by["llm/grant"] + by["llm/drain"]:
            assert r["args"]["eager_us"] >= 0.0, r
            assert r["args"]["eager_us"] <= r["dur"] + 1.0, r
        for g in by["llm/grant"]:
            if kind == "paged":
                assert (g["args"]["eager_us"] > 0) == (g["args"]["pages"]
                                                       > 0), g
        for d in by["llm/drain"]:
            assert (d["args"]["eager_us"] > 0) == (d["args"]["finished"]
                                                   > 0), d

    def test_disabled_the_new_code_reads_no_clock(self, model,
                                                  monkeypatch):
        """Observability off: no record, no ``gc`` callback, the eager
        timer reads no clock; the two gap counters still count (int
        arithmetic where the token is applied)."""
        import gc

        from bigdl_tpu import observability as obs
        from bigdl_tpu.llm import serving
        obs.disable()
        try:
            obs.TRACE.clear()
            found = list(gc.callbacks)
            srv = LLMServer(model, max_batch=2, max_seq_len=32)
            reads = []
            real = time.perf_counter

            class Clock:
                """``time`` as the eager timer sees it."""
                @staticmethod
                def perf_counter():
                    reads.append(1)
                    return real()

            timer = serving._EagerTimer()
            monkeypatch.setattr(serving, "time", Clock)
            with timer:
                pass
            monkeypatch.undo()
            assert reads == [] and timer.microseconds() == 0.0
            srv.start()
            try:
                assert gc.callbacks == found
                req = srv.submit(np.array([3, 1, 4], np.int32),
                                 max_new_tokens=5)
                req.get(timeout=120)
            finally:
                srv.stop()
            assert gc.callbacks == found
            assert len(obs.TRACE) == 0
            assert srv._eager.microseconds() == 0.0
            assert srv.token_gaps_total == 4
            assert srv.token_gaps_behind_prefill_total == 0
        finally:
            obs.enable()

    def test_new_span_names_pass_the_registry_gate(self):
        import os

        from bigdl_tpu.analysis import ProjectIndex, registries
        from bigdl_tpu.analysis import registrydrift
        names = set(CHILDREN) | {"py/gc"}
        assert names <= set(registries.SPAN_NAMES)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        emitted = registrydrift.collect_literals(
            ProjectIndex.scan(root, ("bigdl_tpu",))).span
        assert names <= set(emitted)
