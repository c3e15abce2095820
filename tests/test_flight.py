"""ISSUE 16: engine flight recorder + live roofline attribution —
ring semantics, typed decision events recorded at real engine decision
points, the trace-stitched ``/debug/explain/<request_id>`` timeline and
its one-line verdicts, the filterable ``/debug/flight`` ring surface,
the flight-gated utilization sampler (``bigdl_device_mfu`` /
``bigdl_device_hbm_bw_gbps`` / ``bigdl_device_bw_util`` + the roofline
table), and the disabled-mode structural-absence contract for
``bigdl.observability.flight.enabled``."""

import http.client
import json
import sys

import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu.observability import compile_recorder, flight, utilization
from bigdl_tpu.observability import request_context as rc
from bigdl_tpu.utils.conf import conf

GATE = "bigdl.observability.flight.enabled"


@pytest.fixture(autouse=True)
def _flight_clean():
    """Observability on, the flight gate at its default (OFF), and an
    empty ring/sampler around every test; tests opt in via
    ``conf.set(GATE, "true")``. The global registry is NOT cleared (live
    modules hold instrument refs) — absence tests read render deltas."""
    was = obs.enabled()
    obs.enable()
    flight.reset()
    utilization.reset()
    yield
    for key in (GATE, "bigdl.observability.flight.capacity",
                "bigdl.device.peak.tflops", "bigdl.device.peak.gbps"):
        conf.unset(key)
    flight.reset()
    utilization.reset()
    if was:
        obs.enable()
    else:
        obs.disable()


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=120)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read().decode())
    finally:
        conn.close()


class TestFlightRing:
    def test_bounded_oldest_dropped(self):
        r = flight.FlightRing(4)
        for i in range(7):
            r.append({"seq": i, "kind": "queue"})
        assert [e["seq"] for e in r.events()] == [3, 4, 5, 6]
        assert r.dropped == 3 and len(r) == 4

    def test_filters_and_limit(self):
        r = flight.FlightRing(16)
        for i in range(6):
            r.append({"seq": i, "kind": "queue" if i % 2 else "admit",
                      "request": f"r{i % 3}"})
        assert all(e["kind"] == "queue" for e in r.events(kind="queue"))
        assert [e["seq"] for e in r.events(request_id="r1")] == [1, 4]
        assert [e["seq"] for e in r.events(limit=2)] == [4, 5]

    def test_set_capacity_keeps_newest(self):
        r = flight.FlightRing(8)
        for i in range(8):
            r.append({"seq": i, "kind": "queue"})
        r.set_capacity(3)
        assert [e["seq"] for e in r.events()] == [5, 6, 7]
        r.append({"seq": 8, "kind": "queue"})
        assert [e["seq"] for e in r.events()] == [6, 7, 8]


class TestGateStructuralAbsence:
    def test_default_off_record_is_noop_zero_registry_delta(self):
        assert conf.get_bool(GATE, False) is False
        assert flight.enabled is False
        lines = set(obs.render().splitlines())
        flight.record("shed", request_id="r1", component="x")
        flight.record("evict", pages=3)
        assert flight.ring() is None          # never constructed
        assert set(obs.render().splitlines()) == lines

    def test_endpoints_404_when_off(self):
        for path in ("/debug/flight", "/debug/explain/r1"):
            resp = flight.debug_endpoint(path)
            assert resp is not None and resp[0] == 404, \
                f"{path} must 404 while {GATE} is off"
        # unowned paths fall through to the next helper
        assert flight.debug_endpoint("/debug/traces") is None
        assert flight.debug_endpoint("/healthz") is None

    def test_runtime_toggle(self):
        conf.set(GATE, "true")
        assert flight.enabled
        flight.record("queue", request_id="r1")
        assert len(flight.ring()) == 1
        conf.set(GATE, "false")
        assert not flight.enabled
        flight.record("queue", request_id="r2")
        assert len(flight.ring()) == 1        # kept, not grown

    def test_capacity_conf_pokes_live_ring(self):
        conf.set(GATE, "true")
        for i in range(8):
            flight.record("queue", request_id=f"r{i}")
        conf.set("bigdl.observability.flight.capacity", "4")
        assert flight.ring().capacity == 4
        assert len(flight.ring()) == 4


class TestRecordExplain:
    def test_ambient_trace_detail_filter_and_counter(self):
        conf.set(GATE, "true")
        before = obs.REGISTRY.sample_value("bigdl_flight_events_total",
                                           kind="admit") or 0
        ctx = rc.new_trace()
        with rc.activate(ctx):
            flight.record("admit", request_id="req-1", slot=0,
                          matched_tokens=None)
        (ev,) = flight.ring().events()
        assert ev["trace"] == ctx.trace_id    # picked up from context
        assert ev["detail"] == {"slot": 0}    # None-valued keys dropped
        assert obs.REGISTRY.sample_value("bigdl_flight_events_total",
                                         kind="admit") == before + 1

    def test_explain_stitches_trace_and_orders_causally(self):
        """Acceptance: a request hitting radix miss + tier fetches +
        chunked admission + a mid-stream failover resume (recorded by
        the router under its own local id but the same trace) yields
        one causally ordered timeline and the composite verdict."""
        conf.set(GATE, "true")
        tid = "ab" * 16
        flight.record("queue", request_id="w-req", trace_id=tid,
                      prompt_tokens=96)
        flight.record("radix_miss", request_id="w-req", trace_id=tid,
                      prompt_tokens=96)
        flight.record("park", request_id="w-req", trace_id=tid, pages=3)
        flight.record("fetch", request_id="w-req", trace_id=tid,
                      pages=2, wait_ms=21.0, status="landed")
        flight.record("fetch", request_id="w-req", trace_id=tid,
                      pages=1, wait_ms=20.0, status="landed")
        flight.record("admit", request_id="w-req", trace_id=tid,
                      chunked=True)
        for c in (32, 32, 32):
            flight.record("chunk_charge", request_id="w-req",
                          trace_id=tid, chunk_tokens=c)
        flight.record("failover", request_id="router-7", trace_id=tid,
                      tokens_resumed=2, attempt=2)
        flight.record("finish", request_id="w-req", trace_id=tid,
                      tokens=8, ttft_ms=700.0)
        doc = flight.explain("w-req")
        assert doc["traces"] == [tid]
        seqs = [e["seq"] for e in doc["events"]]
        assert seqs == sorted(seqs)                   # causal order
        assert any(e.get("request") == "router-7"
                   for e in doc["events"])            # trace-stitched
        v = doc["verdict"]
        assert v.startswith("slow TTFT")              # 700 > 500 default
        assert "radix miss" in v
        assert "2 tier fetches parked 41 ms" in v
        assert "chunked admission, 3 chunks" in v
        assert "1 mid-stream failover resume" in v
        assert "TTFT 700 ms" in v

    def test_shed_verdict_and_ok_verdict(self):
        conf.set(GATE, "true")
        flight.record("shed", request_id="s1", component="llm_server",
                      reason="queue_full")
        assert flight.explain("s1")["verdict"] == "shed: queue_full"
        flight.record("radix_hit", request_id="h1", matched_tokens=64)
        flight.record("finish", request_id="h1", tokens=4, ttft_ms=12.0)
        v = flight.explain("h1")["verdict"]
        assert v.startswith("ok") and "radix hit (64 tokens reused)" in v

    def test_debug_flight_filters(self):
        conf.set(GATE, "true")
        for i in range(5):
            flight.record("queue" if i % 2 else "evict",
                          request_id=f"r{i}", pages=i)
        st, doc = flight.debug_endpoint("/debug/flight?kind=evict")
        assert st == 200 and doc["kinds"] == ["evict"]
        st, doc = flight.debug_endpoint("/debug/flight?request=r1")
        assert st == 200
        assert all(e["request"] == "r1" for e in doc["events"])
        st, doc = flight.debug_endpoint("/debug/flight?limit=2")
        assert st == 200 and len(doc["events"]) == 2

    def test_explain_unknown_request_404s(self):
        conf.set(GATE, "true")
        flight.record("queue", request_id="known")
        st, body = flight.debug_endpoint("/debug/explain/unknown")
        assert st == 404 and "unknown" in body["error"]


class TestServingEmission:
    def test_engine_decision_points_and_http_surfaces(self):
        """Live engine: a cold and then a warm admission through the
        prefix cache emit queue/admit/radix_miss/radix_hit/finish at
        the real decision points; the worker serves /debug/flight and
        /debug/explain over HTTP, and flipping the gate off turns both
        into 404s without restarting anything."""
        from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                                LlamaForCausalLM)
        from bigdl_tpu.llm.serving import LLMServer
        from bigdl_tpu.llm.worker import LLMWorker

        conf.set(GATE, "true")
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                             max_cache_len=64)
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        kvcache=True).start()
        worker = LLMWorker(srv).start()
        try:
            prompt = np.arange(1, 13, dtype=np.int32)
            r1 = srv.submit(prompt, max_new_tokens=3)
            r1.get(timeout=300)
            r2 = srv.submit(prompt, max_new_tokens=3)
            r2.get(timeout=300)
            kinds1 = {e["kind"]
                      for e in flight.ring().events(request_id=r1.id)}
            assert {"queue", "admit", "radix_miss", "finish"} <= kinds1
            kinds2 = {e["kind"]
                      for e in flight.ring().events(request_id=r2.id)}
            assert "radix_hit" in kinds2
            st, doc = _get(worker.address,
                           f"/debug/explain/{r2.id}")
            assert st == 200
            assert "radix hit" in doc["verdict"]
            assert "TTFT" in doc["verdict"]   # finish stamped ttft_ms
            st, ring_doc = _get(worker.address, "/debug/flight?kind=queue")
            assert st == 200 and ring_doc["kinds"] == ["queue"]
            # runtime off: same process, endpoints now 404
            conf.set(GATE, "false")
            st, _ = _get(worker.address, "/debug/flight")
            assert st == 404
            st, _ = _get(worker.address, f"/debug/explain/{r2.id}")
            assert st == 404
        finally:
            worker.stop()
            srv.stop(drain=False)


class TestUtilization:
    def test_window_math_gauges_and_roofline(self, monkeypatch):
        conf.set(GATE, "true")
        conf.set("bigdl.device.peak.tflops", "100")
        conf.set("bigdl.device.peak.gbps", "800")
        monkeypatch.setattr(compile_recorder, "latest_costs",
                            lambda: {"llm/decode_paged": (2e9, 4e8)})
        for _ in range(10):
            utilization.observe("llm/decode_paged", 0.001)
        snap = utilization.snapshot()
        assert snap["samples"] == 10
        assert snap["peak_tflops"] == 100.0
        assert snap["peak_gbps"] == 800.0
        # 4e8 bytes / 1e-3 s = 400 GB/s; mfu = 2e12/1e14; bw 400/800
        assert snap["hbm_bw_gbps"] == pytest.approx(400.0)
        assert snap["mfu"] == pytest.approx(0.02)
        assert snap["bw_util"] == pytest.approx(0.5)
        (row,) = snap["programs"]
        assert row["fn"] == "llm/decode_paged" and row["calls"] == 10
        # 5 flops/byte << the 125 flops/byte machine balance
        assert row["bound"] == "memory"
        assert obs.REGISTRY.sample_value("bigdl_device_hbm_bw_gbps") \
            == pytest.approx(400.0)
        assert obs.REGISTRY.sample_value("bigdl_device_mfu") \
            == pytest.approx(0.02)
        assert obs.REGISTRY.sample_value("bigdl_device_bw_util") \
            == pytest.approx(0.5)

    def test_compute_bound_classification(self, monkeypatch):
        conf.set(GATE, "true")
        conf.set("bigdl.device.peak.tflops", "100")
        conf.set("bigdl.device.peak.gbps", "800")
        # 2000 flops/byte >> 125: sits on the compute side
        monkeypatch.setattr(compile_recorder, "latest_costs",
                            lambda: {"llm/step_mixed": (2e12, 1e9)})
        utilization.observe("llm/step_mixed", 0.1)
        (row,) = utilization.roofline_table()
        assert row["bound"] == "compute"

    def test_unattributable_programs_excluded_from_window(
            self, monkeypatch):
        conf.set(GATE, "true")
        conf.set("bigdl.device.peak.gbps", "800")
        monkeypatch.setattr(compile_recorder, "latest_costs",
                            lambda: {"known": (0.0, 4e8)})
        utilization.observe("known", 0.001)
        utilization.observe("mystery", 10.0)  # no costs: not in ratio
        assert obs.REGISTRY.sample_value("bigdl_device_hbm_bw_gbps") \
            == pytest.approx(400.0)

    def test_gated_off_structurally_absent(self):
        assert not flight.enabled
        lines = set(obs.render().splitlines())
        utilization.observe("llm/decode_paged", 0.01)
        snap = utilization.snapshot()
        assert snap["samples"] == 0 and snap["programs"] == []
        assert "mfu" not in snap and "bw_util" not in snap
        assert set(obs.render().splitlines()) == lines

    def test_peaks_conf_override_and_unknown_platform(self):
        # CPU backend, no override: both axes unknown, gauges suppressed
        assert utilization.peaks() == (None, None)
        conf.set("bigdl.device.peak.tflops", "197")
        conf.set("bigdl.device.peak.gbps", "819")
        assert utilization.peaks() == (197e12, 819.0)

    def test_peak_spec_keyed_by_exact_device_kind(self):
        """One table (bench.py reads it too): exact ``device_kind``
        lookup, None off-TPU, and a TPU the table does not hold is an
        error rather than the nearest substring's figures."""
        import types

        import bench
        v5e = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        assert utilization.peak_spec(v5e) == (197.0, 819.0)
        assert bench._peak_flops(v5e) == 197e12
        cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
        assert utilization.peak_spec(cpu) is None
        unknown = types.SimpleNamespace(platform="tpu",
                                        device_kind="TPU v5 litest")
        with pytest.raises(ValueError, match="TPU v5 litest"):
            utilization.peak_spec(unknown)


class TestExplainTools:
    def _seed_events(self):
        tid = "cd" * 16
        flight.record("queue", request_id="w-1", trace_id=tid)
        flight.record("radix_miss", request_id="w-1", trace_id=tid)
        flight.record("failover", request_id="router-2", trace_id=tid,
                      tokens_resumed=1)
        flight.record("finish", request_id="w-1", trace_id=tid,
                      tokens=4, ttft_ms=40.0)

    def test_summarize_explain_from_ring_dump(self, tmp_path):
        conf.set(GATE, "true")
        self._seed_events()
        st, ring_doc = flight.debug_endpoint("/debug/flight")
        assert st == 200
        path = tmp_path / "flight.json"
        path.write_text(json.dumps(ring_doc))
        sys.path.insert(0, "tools")
        try:
            from telemetry_report import summarize_explain
        finally:
            sys.path.pop(0)
        out = summarize_explain("w-1", str(path))
        assert out["request"] == "w-1"
        assert any(e.get("request") == "router-2"
                   for e in out["events"])           # stitched offline too
        assert "failover" in out["verdict"]

    def test_summarize_explain_live_ring(self):
        conf.set(GATE, "true")
        self._seed_events()
        sys.path.insert(0, "tools")
        try:
            from telemetry_report import summarize_explain
        finally:
            sys.path.pop(0)
        out = summarize_explain("w-1")
        assert out["verdict"] == flight.explain("w-1")["verdict"]

    def test_explain_report_renders_timeline_and_roofline(
            self, capsys, monkeypatch):
        conf.set(GATE, "true")
        conf.set("bigdl.device.peak.tflops", "100")
        conf.set("bigdl.device.peak.gbps", "800")
        monkeypatch.setattr(compile_recorder, "latest_costs",
                            lambda: {"llm/decode_paged": (2e9, 4e8)})
        utilization.observe("llm/decode_paged", 0.001)
        self._seed_events()
        sys.path.insert(0, "tools")
        try:
            from explain_report import render
        finally:
            sys.path.pop(0)
        render(flight.explain("w-1"), roof=utilization.snapshot())
        text = capsys.readouterr().out
        assert "flight timeline: request w-1" in text
        assert "verdict:" in text
        assert "llm/decode_paged" in text and "roofline" in text


class TestFederationSnapshotRoofline:
    def test_roofline_rides_snapshot_only_when_sampled(self, monkeypatch):
        from bigdl_tpu.observability.federation import registry_snapshot
        doc = registry_snapshot(instance="w0")
        assert "roofline" not in doc          # gate off: no key at all
        conf.set(GATE, "true")
        monkeypatch.setattr(compile_recorder, "latest_costs",
                            lambda: {"llm/decode_paged": (2e9, 4e8)})
        utilization.observe("llm/decode_paged", 0.001)
        doc = registry_snapshot(instance="w0")
        assert doc["roofline"]["programs"][0]["fn"] == "llm/decode_paged"
