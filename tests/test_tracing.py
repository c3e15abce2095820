"""ISSUE 3: end-to-end request tracing + XLA compile/HBM flight
recorder — TraceContext propagation (headers, queue records, contextvar),
cross-process span stitching on both serving stacks, /debug/trace
assembly, latency exemplars, recompile detection, the self-describing
build-info series, and the disabled-mode no-surface contract."""

import http.client
import json
import sys
import time

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import observability as obs
from bigdl_tpu.observability import request_context as rc
from bigdl_tpu.observability.tracing import ExemplarStore

sys.path.insert(0, "tools")
try:
    from trace_report import build_waterfall, render_waterfall, traces_in
finally:
    sys.path.pop(0)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Enabled switch, empty trace ring and exemplar store per test; the
    global registry is NOT cleared (live modules hold instrument refs) —
    tests read deltas."""
    was = obs.enabled()
    obs.enable()
    obs.TRACE.clear()
    obs.EXEMPLARS.clear()
    yield
    obs.TRACE.clear()
    obs.EXEMPLARS.clear()
    if was:
        obs.enable()
    else:
        obs.disable()


def _request(addr, method, path, obj=None, headers=()):
    conn = http.client.HTTPConnection(*addr, timeout=120)
    hdrs = {"Content-Type": "application/json", **dict(headers)}
    conn.request(method, path, json.dumps(obj) if obj is not None
                 else None, hdrs)
    r = conn.getresponse()
    body = r.read()
    out_headers = {k: v for k, v in r.getheaders()}
    conn.close()
    try:
        body = json.loads(body)
    except ValueError:
        body = body.decode()
    return r.status, body, out_headers


class TestTraceContext:
    def test_ids_and_child(self):
        ctx = rc.new_trace()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id
        assert child.parent_id == ctx.span_id

    def test_header_roundtrip_case_insensitive(self):
        ctx = rc.new_trace()
        pairs = rc.to_headers(ctx)
        assert dict(pairs)[rc.TRACE_HEADER] == ctx.trace_id
        # a client lowercasing every header name must still propagate
        lowered = {k.lower(): v for k, v in pairs}
        back = rc.from_headers(lowered)
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id   # arrives as parent-span
        # and SHOUTING-case too
        shouted = {k.upper(): v for k, v in pairs}
        assert rc.from_headers(shouted).trace_id == ctx.trace_id

    def test_wire_roundtrip(self):
        ctx = rc.new_trace()
        blob = rc.to_wire(ctx)
        back = rc.from_wire(blob)
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert rc.from_wire(None) is None
        assert rc.from_wire({"nope": 1}) is None

    def test_disabled_emits_and_extracts_nothing(self):
        ctx = rc.new_trace()
        obs.disable()
        try:
            assert rc.to_headers(ctx) == []
            assert rc.to_wire(ctx) is None
            assert rc.from_headers({rc.TRACE_HEADER: "a" * 32}) is None
            assert rc.server_context({rc.TRACE_HEADER: "a" * 32}) is None
            with rc.activate(ctx):
                assert rc.current() is None
        finally:
            obs.enable()

    def test_server_context_mints_root_when_absent(self):
        ctx = rc.server_context({})
        assert ctx is not None and len(ctx.trace_id) == 32


class TestSpanTraceTagging:
    def test_nested_spans_stitch_under_context(self):
        ctx = rc.new_trace()
        with rc.activate(ctx):
            with obs.span("outer", stage="a"):
                with obs.span("inner", stage="b"):
                    pass
        inner, outer = obs.TRACE.spans()       # completion order
        assert inner["args"]["trace"] == ctx.trace_id
        assert outer["args"]["trace"] == ctx.trace_id
        # inner's parent span is outer's own span id; outer parents to
        # the activated context (the caller's open span)
        assert inner["args"]["parent_span"] == outer["args"]["span"]
        assert outer["args"]["parent_span"] == ctx.span_id
        # context restored after the block
        assert rc.current() is None

    def test_untraced_spans_have_no_trace_args(self):
        with obs.span("plain"):
            pass
        (span,) = obs.TRACE.spans()
        assert "trace" not in span["args"]

    def test_for_trace_and_assemble(self):
        tid = "d" * 32
        obs.add_complete("x", 100.0, 0.5, trace=tid, stage="s1")
        obs.add_complete("y", 100.6, 0.25, trace=tid, stage="s2")
        obs.add_complete("z", 100.0, 0.1, trace="e" * 32, stage="s1")
        spans = obs.TRACE.for_trace(tid)
        assert [s["name"] for s in spans] == ["x", "y"]
        asm = obs.assemble_trace(tid)
        assert asm["span_count"] == 2
        assert set(asm["stages"]) == {"s1", "s2"}
        assert asm["stages"]["s2"]["seconds"] == pytest.approx(0.25)


class TestFakeClockWaterfall:
    def test_three_stage_waterfall_math(self):
        """Frontend→queue→worker stitching verified against a fake
        clock: offsets, durations and stage rollup come out exactly."""
        tid = "f" * 32
        t0 = 1000.0
        obs.add_complete("serving/predict", t0, 0.5, trace=tid,
                         stage="frontend")
        obs.add_complete("serving/queue_wait", t0 + 0.01, 0.2,
                         trace=tid, stage="queue")
        obs.add_complete("serving/infer", t0 + 0.21, 0.25, trace=tid,
                         stage="cluster_serving")
        wf = build_waterfall(obs.TRACE.spans(), tid)
        assert wf["wall_ms"] == pytest.approx(500.0)
        assert [r["name"] for r in wf["rows"]] == \
            ["serving/predict", "serving/queue_wait", "serving/infer"]
        assert wf["rows"][1]["start_ms"] == pytest.approx(10.0)
        assert wf["rows"][1]["dur_ms"] == pytest.approx(200.0)
        assert wf["stages"]["queue"] == pytest.approx(200.0)
        assert wf["stages"]["cluster_serving"] == pytest.approx(250.0)
        text = render_waterfall(wf)
        assert "stage rollup" in text and "queue" in text

    def test_emit_record_trace_spans_fake_clock(self):
        from bigdl_tpu.serving.cluster_serving import \
            emit_record_trace_spans
        tid = "a1" * 16
        recs = [{"uri": "u1", "trace": {"trace_id": tid,
                                        "parent_span": "b" * 16},
                 "enqueued_at": 2000.0},
                {"uri": "u2", "data": {}}]        # untraced: skipped
        shipped = emit_record_trace_spans(recs, infer_start=2003.0,
                                          infer_dur=1.5)
        spans = obs.TRACE.for_trace(tid)
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"serving/queue_wait", "serving/infer"}
        qw = by_name["serving/queue_wait"]
        assert qw["dur"] == pytest.approx(3.0 * 1e6)
        assert qw["args"]["parent_span"] == "b" * 16
        assert by_name["serving/infer"]["dur"] == \
            pytest.approx(1.5 * 1e6)
        assert len(obs.TRACE.spans()) == 2    # untraced rec emitted none
        # the consumer ships its spans home for cross-process assembly
        assert set(shipped) == {"u1"}
        assert [s["name"] for s in shipped["u1"]] == \
            ["serving/queue_wait", "serving/infer"]

    def test_foreign_span_ingestion_by_pid(self):
        import os
        from bigdl_tpu.observability import tracing
        mine = tracing.make_complete("local", 1.0, 0.1, trace="x" * 32)
        foreign = dict(mine, pid=os.getpid() + 1, name="remote")
        tracing.ingest_foreign_spans([mine, foreign, None])
        names = [s["name"] for s in obs.TRACE.spans()]
        assert names == ["remote"]     # same-pid and junk skipped

    def test_result_record_carries_trace_spans_on_the_wire(self):
        """The output-queue record round-trips the consumer's spans
        through the wire protocol (the cross-process assembly path)."""
        from bigdl_tpu.serving.cluster_serving import (
            ClusterServing, InputQueue, OutputQueue)
        from bigdl_tpu.serving.inference_model import InferenceModel

        im = InferenceModel().load_bigdl(
            model=nn.Sequential().add(nn.Linear(4, 2)).add(nn.SoftMax()))
        stream = "trace_wire_stream"
        inq = InputQueue(stream)
        outq = OutputQueue(stream)
        job = ClusterServing(im, stream_name=stream).start()
        ctx = rc.new_trace()
        try:
            with rc.activate(ctx):
                uri = inq.enqueue(None, input=np.ones((1, 4), np.float32))
            deadline = time.time() + 30
            rec = None
            while rec is None and time.time() < deadline:
                rec = outq.dequeue_record(timeout=1.0)
            assert rec is not None and rec["uri"] == uri
            names = [s["name"] for s in rec.get("trace_spans", [])]
            assert "serving/infer" in names
            assert all(s["args"]["trace"] == ctx.trace_id
                       for s in rec["trace_spans"])
        finally:
            job.stop()


class TestFrontendTraceStitching:
    def test_predict_stitches_three_stages(self):
        """Acceptance: one request through ServingFrontend backed by
        ClusterServing yields a single stitched trace, retrievable via
        GET /debug/trace/<id>, covering ≥3 stages — with lowercased
        request headers (the casing satellite)."""
        from bigdl_tpu.serving.cluster_serving import ClusterServing
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        from bigdl_tpu.serving.inference_model import InferenceModel

        im = InferenceModel().load_bigdl(
            model=nn.Sequential().add(nn.Linear(4, 3)).add(nn.SoftMax()))
        job = ClusterServing(im, stream_name="trace_stream").start()
        fe = ServingFrontend(stream_name="trace_stream").start()
        tid = "ab" * 16
        try:
            code, out, headers = _request(
                fe.address, "POST", "/predict",
                {"inputs": {"input": [[1.0, 2.0, 3.0, 4.0]]}},
                headers={"x-bigdl-trace-id": tid,
                         "x-bigdl-parent-span": "cd" * 8})
            assert code == 200, out
            # response echoes the trace id for /debug/trace lookup
            assert headers.get(rc.TRACE_HEADER) == tid
            code, doc, _ = _request(fe.address, "GET",
                                    f"/debug/trace/{tid}")
            assert code == 200
            stages = set(doc["stages"])
            assert {"frontend", "queue", "cluster_serving"} <= stages
            assert doc["span_count"] >= 3
            # the frontend root span parents to the client's span header
            root = [s for s in doc["spans"]
                    if s["name"] == "serving/predict"][0]
            assert root["args"]["parent_span"] == "cd" * 8
            # exemplar retained and listed
            code, ex, _ = _request(fe.address, "GET", "/debug/traces")
            assert code == 200
            assert any(e["trace_id"] == tid for e in ex["exemplars"])
            # the tool renders its waterfall
            wf = build_waterfall(doc["spans"], tid)
            assert wf["wall_ms"] > 0 and len(wf["rows"]) >= 3
            assert "frontend" in wf["stages"]
        finally:
            fe.stop()
            job.stop()

    def test_request_without_headers_gets_fresh_trace(self):
        from bigdl_tpu.serving.cluster_serving import ClusterServing
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        from bigdl_tpu.serving.inference_model import InferenceModel

        im = InferenceModel().load_bigdl(
            model=nn.Sequential().add(nn.Linear(4, 2)).add(nn.SoftMax()))
        job = ClusterServing(im, stream_name="trace_fresh_stream").start()
        fe = ServingFrontend(stream_name="trace_fresh_stream").start()
        try:
            code, _, headers = _request(
                fe.address, "POST", "/predict",
                {"inputs": {"input": [[1.0, 2.0, 3.0, 4.0]]}})
            assert code == 200
            tid = headers.get(rc.TRACE_HEADER)
            assert tid and len(tid) == 32
            assert obs.TRACE.for_trace(tid)
        finally:
            fe.stop()
            job.stop()


class TestDeadlineHeaderCasing:
    def test_lowercase_deadline_header_caps_the_wait(self):
        """X-BigDL-Deadline-Ms must round-trip case-insensitively: a
        lowercased header on a request whose backend never answers must
        cap the wait at the deadline, not the 30s result timeout."""
        from bigdl_tpu.serving.http_frontend import ServingFrontend

        fe = ServingFrontend(stream_name="deadline_case_stream").start()
        try:
            t0 = time.monotonic()
            code, out, _ = _request(
                fe.address, "POST", "/predict",
                {"inputs": {"input": [[1.0, 2.0]]}},
                headers={"x-bigdl-deadline-ms": "300"})
            elapsed = time.monotonic() - t0
            assert code == 504 and "timeout" in out["error"]
            assert elapsed < 10.0    # not the 30s result_timeout
        finally:
            fe.stop()


class TestSpanObject:
    """ISSUE 25: one clock on every record, args filled in while a span
    is open, an early ``end()``, an annotation of the span's own."""

    def test_every_record_carries_its_perf_counter_start(self):
        before = time.perf_counter()
        with obs.span("train/step", step=1) as sp:
            pass
        obs.add_complete("llm/queue_wait", time.time() - 0.5, 0.5)
        obs.add_complete("llm/pass", time.time() - 0.25, 0.25,
                         before - 0.25, step=3)
        after = time.perf_counter()
        first, derived, given = obs.TRACE.spans()
        assert before <= first["t0"] == sp.t0 <= sp.t1 <= after
        assert first["dur"] == pytest.approx((sp.t1 - sp.t0) * 1e6)
        # worked out from the epoch start where the caller read none
        assert derived["t0"] == pytest.approx(after - 0.5, abs=0.05)
        assert given["t0"] == before - 0.25 and given["args"] == {"step": 3}
        json.dumps(obs.TRACE.spans())       # still plain trace events

    def test_args_filled_while_open_and_early_end(self):
        with obs.span("llm/dispatch", slot=1) as sp:
            sp.args["rows"] = 2
            sp.end(fn="llm/decode_paged")
            t1 = sp.t1
            with obs.span("llm/fence_wait"):
                pass            # after the early end: a sibling, not a child
        assert sp.t1 == t1      # the block's exit did nothing more
        sp.end(fn="again")
        disp, wait = obs.TRACE.spans()
        assert disp["name"] == "llm/dispatch" and len(obs.TRACE) == 2
        assert disp["args"] == {"slot": 1, "rows": 2,
                                "fn": "llm/decode_paged"}
        assert "parent" not in wait["args"]
        assert disp["t0"] + disp["dur"] / 1e6 <= wait["t0"]

    def test_span_propagates_exceptions_and_still_records(self):
        with pytest.raises(KeyError):
            with obs.span("llm/grant"):
                raise KeyError("x")
        assert [r["name"] for r in obs.TRACE.spans()] == ["llm/grant"]
        with obs.span("llm/drain"):     # the stack was popped
            pass
        assert "parent" not in obs.TRACE.spans()[-1]["args"]

    @pytest.mark.parametrize("enabled", [True, False])
    def test_annotate_enters_the_profiler_annotation(self, enabled,
                                                     monkeypatch):
        """``annotate=True`` needs no ``configure(jax_passthrough=)`` and
        holds with observability off; a plain span enters none."""
        import jax
        seen = []

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
        if not enabled:
            obs.disable()
        with obs.span("llm/admit", annotate=True, step=7) as sp:
            with obs.span("llm/prefill"):
                pass
        assert seen == [("enter", "llm/admit"), ("exit", "llm/admit")]
        assert len(obs.TRACE) == (2 if enabled else 0)
        assert (sp.t0 is not None) == enabled


class TestGcWatch:
    """ISSUE 35: the collector's pauses, counted always and recorded as
    ``py/gc`` when long; installed by a started engine and by nothing
    else."""

    @pytest.fixture
    def tiny(self):
        from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                                LlamaForCausalLM)
        return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                            max_cache_len=64)

    @staticmethod
    def _gc_records(generation=None):
        return [r for r in obs.TRACE.spans() if r["name"] == "py/gc"
                and generation in (None, r["args"]["generation"])]

    def test_a_full_collection_under_a_started_server(self, tiny):
        import gc
        import threading

        from bigdl_tpu.llm.serving import LLMServer
        from bigdl_tpu.observability import tracing
        found = list(gc.callbacks)
        srv = LLMServer(tiny, max_batch=2, max_seq_len=32).start()
        try:
            assert gc.callbacks.count(tracing._on_gc) == 1
            n2, s2 = (tracing.gc_collections_total[2],
                      tracing.gc_seconds_total[2])
            obs.TRACE.clear()
            before = time.perf_counter()
            gc.collect()
            after = time.perf_counter()
            (rec,) = self._gc_records(generation=2)
        finally:
            srv.stop()
        assert gc.callbacks == found
        assert rec["tid"] == threading.get_ident()
        assert rec["args"]["collected"] >= 0
        assert before <= rec["t0"] <= rec["t0"] + rec["dur"] / 1e6 <= after
        assert rec["dur"] >= tracing.GC_RECORD_SECONDS * 1e6
        assert tracing.gc_collections_total[2] == n2 + 1
        assert tracing.gc_seconds_total[2] - s2 \
            == pytest.approx(rec["dur"] / 1e6)

    def test_nothing_is_installed_with_observability_off(self, tiny):
        import gc

        from bigdl_tpu.llm.serving import LLMServer
        found = list(gc.callbacks)
        obs.disable()
        srv = LLMServer(tiny, max_batch=2, max_seq_len=32).start()
        try:
            assert gc.callbacks == found
            gc.collect()
        finally:
            srv.stop()
        assert gc.callbacks == found
        assert self._gc_records() == []

    def test_installed_by_the_first_watcher_removed_with_the_last(self):
        import gc

        from bigdl_tpu.observability import tracing
        found = list(gc.callbacks)
        tracing.watch_gc()
        tracing.watch_gc()
        assert gc.callbacks == found + [tracing._on_gc]
        tracing.unwatch_gc()
        assert gc.callbacks == found + [tracing._on_gc]
        tracing.unwatch_gc()
        assert gc.callbacks == found
        tracing.unwatch_gc()        # one too many: nothing to remove
        assert gc.callbacks == found

    def test_a_short_collection_is_counted_and_not_recorded(
            self, monkeypatch):
        """The young generations run for microseconds, hundreds of
        times a second: counted by generation, no record; the old
        generation's sweep holds a profiler annotation from start to
        stop."""
        import gc

        import jax

        from bigdl_tpu.observability import tracing
        # the calls below are made by hand: a real collection between
        # them (a watcher another test's engine left) would take stamps
        gc.disable()
        try:
            self._by_hand(monkeypatch, jax, tracing)
        finally:
            gc.enable()

    def _by_hand(self, monkeypatch, jax, tracing):
        seen = []

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
        stamps = iter([10.0, 10.0002, 20.0, 20.25, 30.0, 30.1])

        class Clock:
            """``time`` as ``tracing`` alone sees it."""
            time = staticmethod(time.time)
            perf_counter = staticmethod(lambda: next(stamps))

        monkeypatch.setattr(tracing, "time", Clock)
        n = list(tracing.gc_collections_total)
        s = list(tracing.gc_seconds_total)
        for gen, collected in ((0, 3), (2, 40)):
            tracing._on_gc("start", {"generation": gen, "collected": 0,
                                     "uncollectable": 0})
            tracing._on_gc("stop", {"generation": gen,
                                    "collected": collected,
                                    "uncollectable": 0})
        # a stop whose start was not seen (watched from mid-collection)
        tracing._on_gc("stop", {"generation": 1, "collected": 0,
                                "uncollectable": 0})
        (rec,) = self._gc_records()
        assert rec["t0"] == 20.0 and rec["dur"] == pytest.approx(0.25e6)
        assert rec["args"] == {"generation": 2, "collected": 40}
        assert seen == [("enter", "py/gc"), ("exit", "py/gc")]
        assert [a - b for a, b in zip(tracing.gc_collections_total, n)] \
            == [1, 0, 1]
        assert [a - b for a, b in zip(tracing.gc_seconds_total, s)] \
            == pytest.approx([0.0002, 0.0, 0.25])
        # disabled: still counted (a watcher may outlive the switch),
        # nothing appended
        obs.disable()
        obs.TRACE.clear()
        tracing._on_gc("start", {"generation": 1, "collected": 0,
                                 "uncollectable": 0})
        tracing._on_gc("stop", {"generation": 1, "collected": 0,
                                "uncollectable": 0})
        assert len(obs.TRACE) == 0
        assert tracing.gc_collections_total[1] == n[1] + 1


class TestLLMTraceStitching:
    @pytest.fixture(scope="class")
    def served(self):
        from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                                LlamaForCausalLM)
        from bigdl_tpu.llm.serving import LLMServer
        from bigdl_tpu.llm.worker import LLMWorker

        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                             max_cache_len=64)
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        worker = LLMWorker(srv).start()
        yield srv, worker
        worker.stop()
        srv.stop(drain=False)

    def test_generate_stitches_engine_stages(self, served):
        """Acceptance: LLMServer→LLMWorker yields one stitched trace
        (request → queue wait → prefill → decode) via /debug/trace."""
        srv, worker = served
        tid = "e1" * 16
        code, out, headers = _request(
            worker.address, "POST", "/worker_generate",
            {"prompt_ids": [1, 2, 3], "max_new_tokens": 3},
            headers={"x-bigdl-trace-id": tid})
        assert code == 200 and len(out["output_ids"]) == 3
        assert headers.get(rc.TRACE_HEADER) == tid
        code, doc, _ = _request(worker.address, "GET",
                                f"/debug/trace/{tid}")
        assert code == 200
        names = {s["name"] for s in doc["spans"]}
        assert {"llm/request", "llm/queue_wait", "llm/prefill",
                "llm/decode"} <= names
        assert {"llm_worker", "queue", "llm_server"} <= \
            set(doc["stages"])
        # decode span accounts the request's tokens
        decode = [s for s in doc["spans"]
                  if s["name"] == "llm/decode"][0]
        assert decode["args"]["tokens"] == 3
        # exemplar retained
        assert any(e["trace_id"] == tid
                   for e in obs.EXEMPLARS.items())

    def test_unknown_trace_404s(self, served):
        _, worker = served
        code, out, _ = _request(worker.address, "GET",
                                "/debug/trace/" + "0" * 32)
        assert code == 404


class TestCompileRecorder:
    def test_recompile_detected_exactly_once(self):
        import jax.numpy as jnp

        f = obs.compiled(lambda x: x * 3, name="test/recompile_unit")

        def series(metric):
            return obs.REGISTRY.sample_value(
                metric, fn="test/recompile_unit") or 0

        f(jnp.ones((4,)))
        f(jnp.ones((4,)))                 # same signature: cache hit
        assert series("bigdl_xla_compiles_total") == 1
        assert series("bigdl_xla_recompiles_total") == 0
        f(jnp.ones((2, 2)))               # changed shape
        assert series("bigdl_xla_compiles_total") == 2
        assert series("bigdl_xla_recompiles_total") == 1
        f(jnp.ones((2, 2)))               # seen again: no new compile
        assert series("bigdl_xla_recompiles_total") == 1
        stats = [s for s in obs.compile_stats()
                 if s["fn"] == "test/recompile_unit"][0]
        assert stats["compiles"] == 2 and stats["recompiles"] == 1
        # the triggering signature is recorded, human-readable
        assert stats["history"][1]["signature"] == "(float32[2,2])"
        # compile events land in the trace ring too
        assert any(s["name"] == "xla/compile"
                   and s["args"]["fn"] == "test/recompile_unit"
                   and s["args"]["recompile"]
                   for s in obs.TRACE.spans())

    def test_cost_and_memory_harvested(self):
        import jax.numpy as jnp

        f = obs.compiled(lambda x: x @ x, name="test/cost_unit")
        f(jnp.ones((8, 8)))
        flops = obs.REGISTRY.sample_value("bigdl_xla_flops_per_call",
                                          fn="test/cost_unit")
        assert flops and flops > 0
        assert obs.REGISTRY.sample_value(
            "bigdl_xla_bytes_accessed_per_call", fn="test/cost_unit") > 0
        assert obs.REGISTRY.sample_value(
            "bigdl_xla_peak_hbm_bytes", fn="test/cost_unit") > 0
        assert obs.REGISTRY.sample_value(
            "bigdl_xla_compile_seconds", fn="test/cost_unit") == 1

    def test_results_match_plain_jit(self):
        import jax.numpy as jnp

        f = obs.compiled(lambda x, y: x * 2 + y, name="test/value_unit")
        out = f(jnp.arange(4.0), y=jnp.ones((4,)))
        np.testing.assert_allclose(np.asarray(out),
                                   np.arange(4.0) * 2 + 1)

    def test_disabled_mode_no_series_no_signatures(self):
        import jax.numpy as jnp

        obs.disable()
        try:
            f = obs.compiled(lambda x: x + 1, name="test/disabled_unit")
            f(jnp.ones((4,)))
            f(jnp.ones((8,)))             # a "recompile", untracked
        finally:
            obs.enable()
        assert obs.REGISTRY.sample_value(
            "bigdl_xla_compiles_total", fn="test/disabled_unit") in \
            (None, 0)
        assert not [s for s in obs.compile_stats()
                    if s["fn"] == "test/disabled_unit"]
        assert len(obs.TRACE) == 0


class TestDisabledModeNoTraceSurface:
    def test_no_headers_no_spans_no_debug(self):
        """Acceptance: with observability disabled no trace headers are
        emitted and no new series/spans exist; /debug/trace is 404."""
        from bigdl_tpu.serving.cluster_serving import ClusterServing
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        from bigdl_tpu.serving.inference_model import InferenceModel

        obs.disable()
        im = InferenceModel().load_bigdl(
            model=nn.Sequential().add(nn.Linear(4, 2)).add(nn.SoftMax()))
        job = ClusterServing(im, stream_name="trace_off_stream").start()
        fe = ServingFrontend(stream_name="trace_off_stream").start()
        try:
            code, _, headers = _request(
                fe.address, "POST", "/predict",
                {"inputs": {"input": [[1.0, 2.0, 3.0, 4.0]]}},
                headers={"x-bigdl-trace-id": "aa" * 16})
            assert code == 200
            assert rc.TRACE_HEADER not in headers
            assert len(obs.TRACE) == 0
            assert obs.EXEMPLARS.items() == []
            code, _, _ = _request(fe.address, "GET",
                                  "/debug/trace/" + "aa" * 16)
            assert code == 404
            code, _, _ = _request(fe.address, "GET", "/debug/traces")
            assert code == 404
        finally:
            obs.enable()
            fe.stop()
            job.stop()


class TestExemplarStore:
    def test_slowest_n_retained(self):
        store = ExemplarStore(capacity=3)
        for i, dur in enumerate([0.1, 0.5, 0.2, 0.9, 0.05]):
            store.offer(f"trace{i}", dur, name="t")
        kept = [e["duration_s"] for e in store.items()]
        assert kept == [0.9, 0.5, 0.2]    # slowest first, capped at 3

    def test_same_trace_updates_in_place(self):
        store = ExemplarStore(capacity=3)
        store.offer("t1", 0.1)
        store.offer("t1", 0.4)
        assert len(store.items()) == 1
        assert store.items()[0]["duration_s"] == pytest.approx(0.4)

    def test_disabled_records_nothing(self):
        store = ExemplarStore(capacity=3)
        obs.disable()
        try:
            store.offer("t1", 1.0)
        finally:
            obs.enable()
        assert store.items() == []


class TestBuildInfo:
    def test_standard_series_on_render(self):
        from bigdl_tpu.observability import parse_prometheus
        from bigdl_tpu.version import __version__

        parsed = parse_prometheus(obs.render())
        info = parsed["bigdl_build_info"]
        (labels, value), = info.items()
        assert value == 1
        assert dict(labels)["version"] == __version__
        assert "jax_version" in dict(labels)
        assert parsed["process_start_time_seconds"][()] == \
            pytest.approx(obs.PROCESS_START_TIME)

    def test_one_identity_after_the_backend_comes_up(self):
        """A scrape made before JAX had a backend said ``backend=
        "none"``; the next one replaces that series (it failed whenever
        a file that renders first shared a worker with this one)."""
        from bigdl_tpu.observability import parse_prometheus

        obs.render()
        obs.REGISTRY.gauge(
            "bigdl_build_info", "",
            labelnames=("version", "jax_version", "backend")).labels(
                version="0", jax_version="0", backend="before").set(1)
        (labels, _), = parse_prometheus(
            obs.render())["bigdl_build_info"].items()
        assert dict(labels)["backend"] != "before"

    def test_absent_when_disabled(self):
        reg = obs.MetricRegistry()
        # the ensure hook writes to the GLOBAL registry only when
        # enabled; a disabled render must not mint the series fresh
        obs.disable()
        try:
            text = obs.render_prometheus(reg)
            assert "bigdl_build_info" not in text
        finally:
            obs.enable()


class TestBenchRegressTool:
    @staticmethod
    def _write_round(tmp_path, n, resnet, llama):
        ns = {"resnet_img_s": resnet,
              "llama_b1": {"v": llama, "unit": "tokens/sec"}}
        compact = {"metric": "resnet50_imagenet_train_throughput",
                   "value": resnet, "unit": "images/sec/chip",
                   "extra": {"northstar_summary": ns}}
        tail = json.dumps(compact)
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "cmd": "bench", "rc": 0, "tail": tail}))

    def test_deltas_and_warn_threshold(self, tmp_path):
        sys.path.insert(0, "tools")
        try:
            from bench_regress import compare_latest
        finally:
            sys.path.pop(0)
        self._write_round(tmp_path, 1, resnet=2500.0, llama=30.0)
        self._write_round(tmp_path, 2, resnet=2550.0, llama=20.0)
        progress = tmp_path / "PROGRESS.jsonl"
        out = compare_latest(str(tmp_path), warn_pct=10.0,
                             progress_path=str(progress))
        assert out["base"] == "BENCH_r01.json"
        assert out["head"] == "BENCH_r02.json"
        d = out["deltas"]
        assert d["resnet_img_s"]["pct"] == pytest.approx(2.0)
        assert not d["resnet_img_s"]["warn"]
        assert d["llama_b1"]["warn"]          # -33%: past the threshold
        assert out["warned"] == ["llama_b1"]
        # compact breadcrumb appended
        line = json.loads(progress.read_text().strip())
        assert line["kind"] == "bench_regress"
        assert line["warned"] == ["llama_b1"]

    def test_fewer_than_two_rounds(self, tmp_path):
        sys.path.insert(0, "tools")
        try:
            from bench_regress import compare_latest
        finally:
            sys.path.pop(0)
        self._write_round(tmp_path, 1, resnet=1.0, llama=1.0)
        assert compare_latest(str(tmp_path)) is None


class TestTelemetryReportTraceFilter:
    def test_trace_filter_and_p95(self):
        sys.path.insert(0, "tools")
        try:
            from telemetry_report import summarize_trace
        finally:
            sys.path.pop(0)
        t1, t2 = "a" * 32, "b" * 32
        for i in range(10):
            obs.add_complete("phase/x", 100.0 + i, 0.01 * (i + 1),
                             trace=t1)
        obs.add_complete("phase/x", 200.0, 5.0, trace=t2)
        doc = {"traceEvents": obs.TRACE.spans()}
        all_spans = summarize_trace(doc)
        assert all_spans["spans"]["phase/x"]["count"] == 11
        assert "p95" in all_spans["spans"]["phase/x"]
        only_t1 = summarize_trace(doc, trace_id=t1)
        assert only_t1["trace_id"] == t1
        assert only_t1["spans"]["phase/x"]["count"] == 10
        assert only_t1["spans"]["phase/x"]["max"] == pytest.approx(0.1)
