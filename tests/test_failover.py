"""Request-level failover, hedged dispatch, and the engine watchdog
(ISSUE 7): journal/prober/hedge units, `LLMRouter._pick` edge cases
(the satellite matrix: breaker skipping, all-open shed, single-backend
pools, live pool mutation), deadline re-derivation on retries, live
mid-stream failover parity, and the disabled-mode structural-absence
contract.

Live-engine tests pre-warm every compiled shape before arming faults:
an XLA compile is indistinguishable from a hung step host-side, so an
unwarmed engine under a tight watchdog would trip on the compile, not
the injected stall (see LLMServer._watchdog_loop)."""

import http.client
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu import reliability as rel
from bigdl_tpu.llm.failover import (Canceller, HealthProber, HedgePolicy,
                                    JournalEntry, LatencyTracker,
                                    RequestJournal, run_hedged)
from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer
from bigdl_tpu.llm.worker import LLMRouter, LLMWorker
from bigdl_tpu.utils.conf import conf

pytestmark = pytest.mark.failover


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=128)


@pytest.fixture()
def faults_armed():
    """Reliability enabled for the test, restored after — later suites
    rely on the process-global default (plain ``disable()`` here would
    silently no-op every later ``set_plan``)."""
    was = rel.enabled()
    if not was:
        rel.enable()
    yield
    rel.set_plan(None)
    if not was:
        rel.disable()


def _generate(model, p, n):
    return model.generate(np.asarray(p)[None], max_new_tokens=n)[0, len(p):]


def _req(addr, method, path, body=None, headers=None, timeout=120):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        payload = json.dumps(body) if body is not None else None
        conn.request(method, path, payload,
                     dict(headers or {},
                          **({"Content-Type": "application/json"}
                             if body is not None else {})))
        r = conn.getresponse()
        data = json.loads(r.read().decode())
        return r.status, data, dict(r.getheaders())
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# units: journal, latency tracker, hedge policy, run_hedged, canceller
# ---------------------------------------------------------------------------

class TestRequestJournal:
    def test_entry_resume_state(self):
        j = RequestJournal()
        ent = j.add([1, 2, 3], max_new_tokens=5)
        assert ent.remaining == 5
        ent.drained([10, 11])
        assert ent.remaining == 3
        # cumulative re-delivery (a hedge twin behind the winner) is a
        # no-op, never a duplicate append
        ent.drained([10])
        ent.drained([10, 11])
        assert ent.tokens == [10, 11]
        # the re-dispatch prompt: original prompt + everything drained
        assert ent.resume_prompt() == [1, 2, 3, 10, 11]
        assert j.inflight() == 1
        j.record_failover(ent)
        assert j.failovers == 1 and j.tokens_resumed == 2
        j.complete(ent)
        assert j.inflight() == 0 and j.completed == 1
        # snapshot of an empty journal is empty (healthz body)
        assert j.snapshot() == []

    def test_snapshot_fields(self):
        j = RequestJournal()
        ent = j.add([1], 4)
        ent.drained([9])
        (snap,) = j.snapshot()
        assert snap["tokens_drained"] == 1
        assert snap["prompt_tokens"] == 1


class TestLatencyTracker:
    def test_quantile_empty_and_window(self):
        t = LatencyTracker(maxlen=4)
        assert t.quantile() is None
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):   # 1.0 rolls out
            t.record(v)
        assert len(t) == 4
        assert t.quantile(0.95) == 100.0
        assert t.quantile(0.0) == 2.0


class TestHedgePolicy:
    def test_disabled_never_allows(self):
        p = HedgePolicy(enabled=False)
        assert not p.allow()

    def test_budget_caps_hedges(self):
        p = HedgePolicy(enabled=True, budget=0.5)
        p.note_request()
        p.note_request()
        # cap = 0.5 * 2 + 1 = 2 hedges
        assert p.allow()
        p.note_hedge()
        assert p.allow()
        p.note_hedge()
        assert not p.allow()

    def test_delay_pinned_vs_p95(self):
        t = LatencyTracker()
        pinned = HedgePolicy(enabled=True, delay_ms=7.0)
        assert pinned.delay_for(t) == pytest.approx(0.007)
        derived = HedgePolicy(enabled=True, min_delay_ms=50.0)
        # no samples -> the floor
        assert derived.delay_for(t) == pytest.approx(0.05)
        t.record(0.2)
        assert derived.delay_for(t) == pytest.approx(0.2)
        # observed p95 under the floor -> floored
        t2 = LatencyTracker()
        t2.record(0.001)
        assert derived.delay_for(t2) == pytest.approx(0.05)


class TestRunHedged:
    def test_fast_primary_never_hedges(self):
        launched = []
        out, outcome = run_hedged(
            lambda c: "fast", lambda c: launched.append(1) or "hedge",
            delay=0.2)
        assert out == "fast" and outcome == "primary"
        assert not launched

    def test_hedge_wins_and_primary_cancelled(self):
        release = threading.Event()
        cancelled = []

        def slow_primary(c):
            cancelled.append(c)
            release.wait(5.0)
            return "slow"

        out, outcome = run_hedged(slow_primary, lambda c: "hedge",
                                  delay=0.01)
        assert out == "hedge" and outcome == "hedge_won"
        assert cancelled[0].cancelled   # the straggler was cancelled
        release.set()

    def test_primary_won_after_hedge_launched(self):
        gate = threading.Event()

        def primary(c):
            gate.wait(5.0)
            return "primary"

        def hedge(c):
            gate.set()            # primary finishes the moment we start
            time.sleep(0.2)
            return "hedge"

        out, outcome = run_hedged(primary, hedge, delay=0.01)
        assert out == "primary" and outcome == "primary_won"

    def test_fast_failure_is_not_hedged(self):
        """A primary that FAILS before the delay propagates: hedging
        tames stragglers, failover handles failures."""
        launched = []

        def bad(c):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            run_hedged(bad, lambda c: launched.append(1) or "x",
                       delay=0.5)
        assert not launched

    def test_both_fail_raises_last(self):
        def bad(c):
            time.sleep(0.05)
            raise RuntimeError("dead")

        with pytest.raises(RuntimeError, match="dead"):
            run_hedged(bad, bad, delay=0.01)

    def test_both_fail_prefers_verdict_errors(self):
        """A backend's relay-worthy verdict (4xx/shed, modeled here
        as ValueError) must not be masked by the twin's LATER
        transport error — the router relays verdicts but burns
        failover attempts on transport errors."""
        def fatal_fast(c):
            raise ValueError("403 from backend")

        def transport_slow(c):
            time.sleep(0.1)
            raise RuntimeError("conn torn")

        with pytest.raises(ValueError, match="403"):
            run_hedged(transport_slow, fatal_fast, delay=0.0,
                       prefer=(ValueError,))
        # without prefer= the temporally-last error still wins
        with pytest.raises(RuntimeError, match="torn"):
            run_hedged(transport_slow, fatal_fast, delay=0.0)

    def test_hedge_callback_fires(self):
        fired = []
        out, outcome = run_hedged(
            lambda c: time.sleep(0.1) or "a", lambda c: "b",
            delay=0.01, on_hedge=lambda: fired.append(1))
        assert fired == [1]
        assert outcome in ("primary_won", "hedge_won")


class TestCanceller:
    class _Conn:
        closed = False

        def close(self):
            self.closed = True

    def test_cancel_closes_attached(self):
        c = Canceller()
        conn = self._Conn()
        c.attach(conn)
        c.cancel()
        assert conn.closed and c.cancelled

    def test_attach_after_cancel_closes_immediately(self):
        c = Canceller()
        c.cancel()
        conn = self._Conn()
        c.attach(conn)
        assert conn.closed


# ---------------------------------------------------------------------------
# derived Retry-After (satellite)
# ---------------------------------------------------------------------------

class TestRetryAfter:
    def test_scales_with_depth_and_clamps(self):
        import random
        rng = random.Random(0)
        conf.set("bigdl.llm.retry_after.jitter", "0")
        try:
            assert rel.retry_after_seconds(0, rng) == "1"
            assert rel.retry_after_seconds(8, rng) == "3"   # 1 + .25*8
            assert rel.retry_after_seconds(10_000, rng) == "30"  # cap
        finally:
            conf.unset("bigdl.llm.retry_after.jitter")

    def test_jitter_bounded_and_depth0_compat(self):
        import random
        # depth 0 with default knobs must still render "1" for every
        # jitter draw (base 1.0 stretched < 1.2 rounds to 1): existing
        # clients see no change until pressure builds
        for seed in range(20):
            assert rel.retry_after_seconds(0, random.Random(seed)) == "1"
        vals = {int(rel.retry_after_seconds(8, random.Random(s)))
                for s in range(20)}
        assert vals <= {3, 4} and len(vals) >= 1   # jittered upward only

    def test_cap_jitters_downward(self):
        """At saturation the jitter spreads BELOW the cap — stretching
        upward and clamping would hand every shed client exactly the
        cap, re-synchronizing the herd at the deepest backlog."""
        import random
        vals = {int(rel.retry_after_seconds(10_000, random.Random(s)))
                for s in range(30)}
        assert max(vals) <= 30
        assert min(vals) >= 24          # cap * (1 - jitter)
        assert len(vals) > 1            # the herd actually spreads


# ---------------------------------------------------------------------------
# health prober
# ---------------------------------------------------------------------------

class TestHealthProber:
    def test_probe_live_and_dead(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=32,
                        page_size=8).start()
        w = LLMWorker(srv).start()
        dead = ("127.0.0.1", 1)
        seen = []
        try:
            prober = HealthProber(
                lambda: [(w.address, "decode"), (dead, "decode")],
                timeout=2.0,
                on_probe=lambda a, r, h, b: seen.append((a, h)))
            # unprobed backends default healthy (a just-added member
            # must be routable before the first sweep)
            assert prober.healthy(w.address) and prober.healthy(dead)
            prober.probe_now()
            assert prober.healthy(w.address)
            assert not prober.healthy(dead)
            assert prober.status()[f"{dead[0]}:{dead[1]}"] is False
            assert dict(seen)[w.address] is True
            prober.forget(dead)
            assert prober.healthy(dead)   # back to the default
        finally:
            w.stop()
            srv.stop()


# ---------------------------------------------------------------------------
# LLMRouter._pick edge cases (satellite)
# ---------------------------------------------------------------------------

def _open_breaker(router, addr):
    b = router._breakers[addr]
    while b.state != "open":
        b.record_failure()


class TestRouterPick:
    def _router(self, n_decode=3, **kw):
        decode = [("127.0.0.1", 10_000 + i) for i in range(n_decode)]
        return LLMRouter([], decode, start_prober=False, **kw)

    def test_round_robin_skips_open_breakers(self):
        r = self._router(3)
        try:
            a, b, c = r.decode_workers
            _open_breaker(r, b)
            picks = [r._pick("decode") for _ in range(4)]
            assert b not in picks
            assert picks == [a, c, a, c]   # rotation continues past b
        finally:
            r.stop()

    def test_all_open_returns_none(self):
        r = self._router(2)
        try:
            for addr in r.decode_workers:
                _open_breaker(r, addr)
            assert r._pick("decode") is None
        finally:
            r.stop()

    def test_single_backend_pool(self):
        r = self._router(1)
        try:
            (only,) = r.decode_workers
            assert r._pick("decode") == only
            assert r._pick("decode") == only
            _open_breaker(r, only)
            assert r._pick("decode") is None
            # empty prefill pool never yields a backend
            assert r._pick("prefill") is None
        finally:
            r.stop()

    def test_exclude_is_soft(self):
        """Excluding every live backend must fall back to retrying
        them, not fail the request outright."""
        r = self._router(2)
        try:
            a, b = r.decode_workers
            assert r._pick("decode", exclude={a}) == b
            assert r._pick("decode", exclude={a, b}) in (a, b)
        finally:
            r.stop()

    def test_prober_unhealthy_skipped(self):
        r = self._router(2, failover=True)
        try:
            a, b = r.decode_workers
            with r._prober._lock:
                r._prober._status[a] = False
            assert r._pick("decode") == b
            assert r._pick("decode") == b
            with r._prober._lock:
                r._prober._status[a] = True
            assert a in {r._pick("decode"), r._pick("decode")}
        finally:
            r.stop()

    def test_pool_mutation_mid_stream(self):
        """The admin surface mutates pools under _pick's lock: a new
        member is picked immediately, a removed one never again, and
        the last decode backend is protected."""
        r = self._router(1, failover=True)
        try:
            (orig,) = r.decode_workers
            added = ("127.0.0.1", 10_099)
            code, out = r._admin_backends(
                {"action": "add", "role": "decode",
                 "host": added[0], "port": added[1]})
            assert code == 200 and len(out["decode_workers"]) == 2
            assert added in r._breakers
            picks = {r._pick("decode") for _ in range(4)}
            assert picks == {orig, added}
            code, _ = r._admin_backends(
                {"action": "remove", "role": "decode",
                 "host": orig[0], "port": orig[1]})
            assert code == 200
            assert all(r._pick("decode") == added for _ in range(3))
            assert orig not in r._breakers   # breaker GC'd with it
            with pytest.raises(ValueError, match="last"):
                r._admin_backends(
                    {"action": "remove", "role": "decode",
                     "host": added[0], "port": added[1]})
        finally:
            r.stop()

    def test_admin_validates(self):
        r = self._router(1, failover=True)
        try:
            with pytest.raises(ValueError):
                r._admin_backends({"action": "nope", "role": "decode"})
            with pytest.raises(ValueError):
                r._admin_backends({"action": "add", "role": "router"})
        finally:
            r.stop()


# ---------------------------------------------------------------------------
# router HTTP surfaces: all-open shed, healthz body, admin endpoint
# ---------------------------------------------------------------------------

class TestRouterSurfaces:
    def test_all_backends_open_sheds_503_with_retry_after(self):
        dead = [("127.0.0.1", 1), ("127.0.0.1", 2)]
        r = LLMRouter([], dead, start_prober=False).start()
        try:
            for addr in dead:
                _open_breaker(r, addr)
            st, body, hdrs = _req(r.address, "POST", "/worker_generate",
                                  {"prompt_ids": [1, 2],
                                   "max_new_tokens": 2})
            assert st == 503
            assert int(hdrs["Retry-After"]) >= 1
            # healthz mirrors the dead pool BEFORE any request fails
            # (satellite): per-backend breaker states in the body
            st, hz, _ = _req(r.address, "GET", "/healthz")
            assert st == 503
            assert set(hz["backends"].values()) == {"open"}
        finally:
            r.stop()

    def test_healthz_includes_prober_and_journal(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=32,
                        page_size=8).start()
        w = LLMWorker(srv, role="decode").start()
        r = LLMRouter([], [w.address], failover=True,
                      start_prober=False).start()
        try:
            r._prober.probe_now()
            st, hz, _ = _req(r.address, "GET", "/healthz")
            assert st == 200
            key = f"{w.address[0]}:{w.address[1]}"
            assert hz["backends"][key] == "closed"
            assert hz["prober"][key] is True
            assert hz["journal_inflight"] == 0
            assert hz["failovers"] == 0
        finally:
            r.stop()
            w.stop()
            srv.stop()

    def test_admin_endpoint_requires_failover(self):
        r = LLMRouter([], [("127.0.0.1", 1)], start_prober=False).start()
        try:
            st, _, _ = _req(r.address, "POST", "/backends",
                            {"action": "add", "role": "decode",
                             "host": "127.0.0.1", "port": 2})
            assert st == 404   # PR 6 router had no such surface
        finally:
            r.stop()

    def test_admin_endpoint_over_http(self):
        r = LLMRouter([], [("127.0.0.1", 1)], failover=True,
                      start_prober=False).start()
        try:
            st, out, _ = _req(r.address, "POST", "/backends",
                              {"action": "add", "role": "decode",
                               "host": "127.0.0.1", "port": 2})
            assert st == 200 and len(out["decode_workers"]) == 2
            st, ws, _ = _req(r.address, "GET", "/worker_get_status")
            assert len(ws["decode_pool"]) == 2
        finally:
            r.stop()


# ---------------------------------------------------------------------------
# deadline re-derivation on retries (satellite)
# ---------------------------------------------------------------------------

class _RecordingBackend:
    """Stub decode worker: records each attempt's deadline header,
    burns a little budget, then fails the stream so the router
    retries."""

    def __init__(self):
        self.deadlines = []
        backend = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                backend.deadlines.append(
                    self.headers.get(rel.DEADLINE_HEADER))
                time.sleep(0.05)          # burn budget between attempts
                body = json.dumps({"error": "injected 500"}).encode()
                self.send_response(500)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.address = self.httpd.server_address
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestDeadlineRederivation:
    def test_each_attempt_sees_remaining_budget(self):
        be = _RecordingBackend()
        r = LLMRouter([], [be.address], failover=True,
                      failover_attempts=3, start_prober=False,
                      breaker_threshold=10).start()
        try:
            st, body, _ = _req(
                r.address, "POST", "/worker_generate",
                {"prompt_ids": [1, 2], "max_new_tokens": 2},
                headers={rel.DEADLINE_HEADER: "5000"})
            assert st == 502    # every attempt failed
            got = [int(d) for d in be.deadlines]
            assert len(got) == 3
            # strictly shrinking, never the original value relayed
            assert got[0] <= 5000
            assert got[1] < got[0] and got[2] < got[1]
            assert got[0] - got[2] >= 90   # two 50 ms sleeps burned
        finally:
            r.stop()
            be.stop()

    def test_expired_deadline_stops_routing(self):
        be = _RecordingBackend()
        r = LLMRouter([], [be.address], failover=True,
                      failover_attempts=10, start_prober=False,
                      breaker_threshold=100).start()
        try:
            st, body, _ = _req(
                r.address, "POST", "/worker_generate",
                {"prompt_ids": [1], "max_new_tokens": 2},
                headers={rel.DEADLINE_HEADER: "120"})
            assert st in (502, 504)
            if st == 504:
                assert "deadline" in body["error"]
            # the 120 ms budget permits at most ~2 of the 10 attempts
            assert len(be.deadlines) <= 3
        finally:
            r.stop()
            be.stop()


class _TimeoutStreamBackend:
    """Stub decode worker whose stream ends in a ``finish_reason:
    "timeout"`` terminal chunk — the silent-truncation verdict a worker
    emits when its stream wait expires on a wedged engine."""

    def __init__(self, tokens=()):
        self.hits = 0
        backend = self
        payload = (json.dumps(
            {"output_ids": list(tokens), "done": True,
             "finish_reason": "timeout"}) + "\n").encode()

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                backend.hits += 1
                self.send_response(200)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.address = self.httpd.server_address
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestTimeoutChunkFailsOver:
    def test_timeout_terminal_chunk_is_retriable(self, model):
        """A backend answering ``finish_reason: "timeout"`` (stream
        wait expired on a wedged engine) must be failed over, not
        relayed as a 200 with truncated/empty output — that silent
        empty answer is exactly the stalled-worker case the journal
        exists for."""
        prompt = list(range(5, 17))
        want = list(map(int, _generate(model, np.asarray(prompt,
                                                         np.int32), 4)))
        stub = _TimeoutStreamBackend()
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=8).start()
        w = LLMWorker(srv, role="decode").start()
        r = LLMRouter([], [stub.address, w.address], failover=True,
                      start_prober=False).start()
        try:
            st, body, _ = _req(r.address, "POST", "/worker_generate",
                               {"prompt_ids": prompt,
                                "max_new_tokens": 4})
            assert stub.hits == 1           # round-robin hit the stub
            assert st == 200
            assert body["output_ids"] == want
            assert body["finish_reason"] != "timeout"
            assert r.failovers == 1
        finally:
            r.stop()
            w.stop()
            srv.stop()
            stub.stop()


# ---------------------------------------------------------------------------
# live failover: mid-stream worker death -> resume parity (tentpole)
# ---------------------------------------------------------------------------

class TestLiveFailover:
    def test_midstream_failure_resumes_bit_identical(self, model,
                                                     faults_armed):
        prompt = list(range(1, 21))
        want = list(map(int, _generate(model, np.asarray(prompt,
                                                         np.int32), 6)))
        s1 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                       kvcache=True).start()
        s2 = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                       kvcache=True).start()
        w1 = LLMWorker(s1, role="decode").start()
        w2 = LLMWorker(s2, role="decode").start()
        r = LLMRouter([], [w1.address, w2.address], failover=True,
                      start_prober=False).start()
        try:
            # failover-path routing with no faults armed
            st, body, _ = _req(r.address, "POST", "/worker_generate",
                               {"prompt_ids": prompt,
                                "max_new_tokens": 6})
            assert st == 200 and body["output_ids"] == want
            assert r.failovers == 0

            # mid-stream kill: the dispatch site raises after chunks
            # drained (llm.step slowed so chunks arrive one token at a
            # time -> the kill lands mid-generation deterministically)
            plan = rel.FaultPlan(seed=0)
            plan.add("router.dispatch", "raise", times=1, after=2)
            plan.add("llm.step", "delay", times=None, delay=0.03)
            rel.set_plan(plan)
            try:
                st, body, _ = _req(r.address, "POST",
                                   "/worker_generate",
                                   {"prompt_ids": prompt,
                                    "max_new_tokens": 6})
            finally:
                rel.set_plan(None)
            assert st == 200
            assert body["output_ids"] == want    # bit-identical resume
            assert r.failovers >= 1
            assert r.tokens_resumed >= 1         # resumed, not restarted
            st, hz, _ = _req(r.address, "GET", "/healthz")
            assert hz["failovers"] == r.failovers
        finally:
            r.stop()
            w1.stop()
            w2.stop()
            s1.stop()
            s2.stop()

    def test_hedged_decode_parity(self, model):
        """Hedge armed with a tiny pinned delay: the duplicate races
        the primary on the twin backend; greedy parity holds no matter
        which side wins, and the hedge counters move."""
        prompt = list(range(30, 45))
        want = list(map(int, _generate(model, np.asarray(prompt,
                                                         np.int32), 5)))
        s1 = LLMServer(model, max_batch=2, max_seq_len=64,
                       page_size=8).start()
        s2 = LLMServer(model, max_batch=2, max_seq_len=64,
                       page_size=8).start()
        w1 = LLMWorker(s1, role="decode").start()
        w2 = LLMWorker(s2, role="decode").start()
        r = LLMRouter([], [w1.address, w2.address], failover=True,
                      hedge=True, hedge_delay_ms=1.0,
                      start_prober=False).start()
        try:
            st, body, _ = _req(r.address, "POST", "/worker_generate",
                               {"prompt_ids": prompt,
                                "max_new_tokens": 5})
            assert st == 200 and body["output_ids"] == want
            assert r.hedges_issued >= 1
        finally:
            r.stop()
            w1.stop()
            w2.stop()
            s1.stop()
            s2.stop()


class TestStreamEosWindow:
    def test_chunk_ending_in_eos_is_always_terminal(self, model,
                                                    faults_armed):
        """A stream chunk whose cumulative tokens end in EOS must carry
        done:true. A done:false chunk with EOS would let a mid-stream
        failover journal the EOS and resume PAST it on another backend,
        generating spurious tokens — the bit-identical contract dies."""
        prompt = np.arange(1, 13, dtype=np.int32)
        toks = list(map(int, _generate(model, prompt, 6)))
        # "EOS" mid-generation: a token that does not occur before its
        # own index, or the run ends earlier than the test expects
        fresh = [i for i in range(1, len(toks)) if toks[i] not in toks[:i]]
        assert fresh, f"greedy run {toks} never leaves its first token"
        eos = toks[fresh[0]]
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        eos_token_id=eos).start()
        w = LLMWorker(srv, role="decode").start()
        plan = rel.FaultPlan(seed=0)
        # one token per chunk: widens the EOS->done.set() window the
        # handler must mask
        plan.add("llm.step", "delay", times=None, delay=0.03)
        rel.set_plan(plan)
        try:
            conn = http.client.HTTPConnection(*w.address, timeout=120)
            try:
                conn.request("POST", "/worker_generate_stream",
                             json.dumps({"prompt_ids":
                                         [int(t) for t in prompt],
                                         "max_new_tokens": 6}),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200
                chunks = []
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    line = line.strip()
                    if line:
                        chunks.append(json.loads(line.decode()))
                    if chunks and chunks[-1].get("done"):
                        break
            finally:
                conn.close()
            for c in chunks:
                ids = c.get("output_ids", [])
                if ids and ids[-1] == eos:
                    assert c["done"], \
                        "non-terminal chunk carried the EOS token"
            assert chunks[-1]["done"]
            assert chunks[-1]["finish_reason"] == "stop"
            assert chunks[-1]["output_ids"] == toks[:fresh[0] + 1]
        finally:
            rel.set_plan(None)
            w.stop()
            srv.stop()


# ---------------------------------------------------------------------------
# engine watchdog
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_stall_fails_pending_retriably_then_recovers(self, model,
                                                         faults_armed):
        prompt = np.arange(1, 13, dtype=np.int32)
        srv = LLMServer(model, max_batch=2, max_seq_len=64, page_size=8,
                        watchdog_timeout=0.25).start()
        try:
            assert srv.watchdog_enabled
            assert srv._watchdog_thread is not None
            # warm every shape the test will hit: a compile stalls the
            # heartbeat exactly like a hung step (see _watchdog_loop)
            srv.submit(prompt, max_new_tokens=2).get(timeout=600)
            trips0 = srv.watchdog_trips
            plan = rel.FaultPlan(seed=0)
            plan.add("worker.stall", "delay", times=1, delay=1.2)
            rel.set_plan(plan)
            try:
                req = srv.submit(prompt, max_new_tokens=8)
                with pytest.raises(RuntimeError, match="watchdog"):
                    req.get(timeout=30)
                assert req.cancel_requested
                assert srv.watchdog_trips > trips0
                # recovery: the heartbeat resumes once the stalled pass
                # completes, the tripped flag clears, service resumes
                deadline = time.monotonic() + 10
                while srv.watchdog_tripped and \
                        time.monotonic() < deadline:
                    time.sleep(0.05)
                assert not srv.watchdog_tripped
            finally:
                rel.set_plan(None)
            out = srv.submit(prompt, max_new_tokens=2).get(timeout=600)
            assert len(out) == 2
        finally:
            srv.stop()

    def test_submit_while_tripped_fails_fast_retriably(self, model):
        """While the episode lasts, new submits must not queue behind
        the wedged pass (they would hang until the stream wait expires
        and surface as a silent 200 timeout) — they fail immediately
        with the same retriable verdict as the trip sweep. Unstarted
        server: no monitor loop to race the manually-set flag. The
        gate needs BOTH the flag and a currently-stale heartbeat —
        the flag alone lags recovery by up to one monitor tick."""
        srv = LLMServer(model, max_batch=2, max_seq_len=32, page_size=8,
                        watchdog_timeout=30.0)
        try:
            srv.watchdog_tripped = True
            srv._hb = time.monotonic() - 60.0   # wedged mid-pass now
            req = srv.submit(np.arange(1, 9, dtype=np.int32),
                             max_new_tokens=4)
            assert req.done.is_set()        # failed fast, never queued
            assert srv._queue.empty()
            with pytest.raises(RuntimeError, match="retriable"):
                req.get(timeout=1)
        finally:
            srv.stop()

    def test_tripped_engine_flips_worker_healthz(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=32, page_size=8,
                        watchdog_timeout=30.0).start()
        w = LLMWorker(srv, role="decode").start()
        try:
            st, hz, _ = _req(w.address, "GET", "/healthz")
            assert st == 200
            assert hz["watchdog"]["tripped"] is False
            srv.watchdog_tripped = True     # what a trip sets
            st, hz, _ = _req(w.address, "GET", "/healthz")
            assert st == 503 and hz["status"] == "stalled"
            # the prober drains a stalled worker out of the pool
            prober = HealthProber(lambda: [(w.address, "decode")])
            prober.probe_now()
            assert not prober.healthy(w.address)
            srv.watchdog_tripped = False
            prober.probe_now()
            assert prober.healthy(w.address)
        finally:
            w.stop()
            srv.stop()

    def test_disabled_watchdog_structurally_absent(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=32,
                        page_size=8).start()
        w = LLMWorker(srv).start()
        try:
            assert not srv.watchdog_enabled
            assert srv._watchdog_thread is None   # no monitor thread
            st, hz, _ = _req(w.address, "GET", "/healthz")
            assert st == 200
            assert "watchdog" not in hz   # healthz body byte-compat
        finally:
            w.stop()
            srv.stop()


# ---------------------------------------------------------------------------
# chaos: the acceptance kill-storm (slow-marked; tier-1 skips it)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_kill_storm_loses_zero_requests():
    """tools/chaos_check.py --failover: seeded mid-stream worker kills
    plus a watchdog-tripping engine stall must complete every request
    with greedy outputs bit-identical to the clean run."""
    from tools.chaos_check import run_failover_chaos

    out = run_failover_chaos(seed=0)
    assert out["match"] and out["lost_requests"] == 0
    assert out["failovers"] > 0


# ---------------------------------------------------------------------------
# disabled mode: the PR 6 router, structurally
# ---------------------------------------------------------------------------

class TestDisabledStructurallyAbsent:
    def test_no_journal_no_prober_no_series(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=8).start()
        w = LLMWorker(srv, role="decode").start()
        before = set(obs.render().splitlines()) if obs.enabled() else set()
        r = LLMRouter([], [w.address], start_prober=False).start()
        try:
            # the gates themselves default off (the gatecheck pass's
            # absence-test contract names the conf keys explicitly)
            assert conf.get_bool("bigdl.llm.failover.enabled",
                                 False) is False
            assert conf.get_bool("bigdl.llm.hedge.enabled",
                                 False) is False
            assert not r._active and not r.failover_enabled
            assert r._journal is None
            assert r._prober is None
            assert r._hedge is None and r._latency is None
            st, body, _ = _req(r.address, "POST", "/worker_generate",
                               {"prompt_ids": list(range(1, 9)),
                                "max_new_tokens": 2})
            assert st == 200 and len(body["output_ids"]) == 2
            # no failover/hedge/journal/prober series appeared from
            # serving through the disabled router
            if obs.enabled():
                new = "\n".join(set(obs.render().splitlines()) - before)
                for name in ("bigdl_router_failovers_total",
                             "bigdl_router_hedges_total",
                             "bigdl_router_journal_inflight",
                             "bigdl_router_backend_healthy"):
                    assert name not in new
            # healthz has no journal/prober keys (PR 6 body shape)
            st, hz, _ = _req(r.address, "GET", "/healthz")
            assert st == 200
            for key in ("journal_inflight", "failovers",
                        "hedges_issued", "prober"):
                assert key not in hz
            # and no prober thread is running for this router
            assert not [t for t in threading.enumerate()
                        if t.name == "bigdl-router-prober"]
        finally:
            r.stop()
            w.stop()
            srv.stop()
