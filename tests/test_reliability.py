"""ISSUE 2 reliability layer: fault injection, policy primitives, atomic
checkpoints, preemption round-trip, serving backpressure.

Everything here is tier-1: retry/breaker schedules run on fake clocks
(zero real sleeping), training cases use tiny MLPs, and the HTTP cases
use the in-proc queue backend.
"""

import http.client
import json
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu import reliability as rel
from bigdl_tpu.reliability.policies import (CircuitBreaker, Deadline,
                                            RetryPolicy)
from bigdl_tpu.utils import checkpoint as ckpt
from bigdl_tpu.utils.conf import conf


@pytest.fixture(autouse=True)
def _clean_reliability_state():
    """Each test starts enabled with no plan armed and no leftover
    health checks; counters reset so assertions are local."""
    rel.enable()
    rel.set_plan(None)
    for name in list(rel.health_checks()):
        rel.unregister_health(name)
    obs.reset()
    yield
    rel.enable()
    rel.set_plan(None)
    for name in list(rel.health_checks()):
        rel.unregister_health(name)
    obs.reset()


def _counter_value(_metric, **labels):
    m = obs.REGISTRY.get(_metric)
    if m is None:
        return 0.0
    child = m.labels(**labels) if labels else m
    return child.value


# ---------------------------------------------------------------------------
# policies: RetryPolicy / Deadline / CircuitBreaker (fake clocks, no sleeps)
# ---------------------------------------------------------------------------

class TestRetryPolicy:
    def test_backoff_schedule_exponential_and_capped(self):
        p = RetryPolicy(max_attempts=6, base_delay=0.1, max_delay=0.5,
                        multiplier=2.0, jitter=0.0, seed=0)
        delays = list(p.delays())
        assert delays == [0.1, 0.2, 0.4, 0.5, 0.5]

    def test_jitter_is_seeded_and_bounded(self):
        a = list(RetryPolicy(max_attempts=4, base_delay=0.1, jitter=0.5,
                             seed=7).delays())
        b = list(RetryPolicy(max_attempts=4, base_delay=0.1, jitter=0.5,
                             seed=7).delays())
        assert a == b                      # same seed, same schedule
        for base, d in zip([0.1, 0.2, 0.4], a):
            assert base <= d <= base * 1.5

    def test_call_retries_then_succeeds_without_sleeping(self):
        slept = []
        p = RetryPolicy(max_attempts=4, base_delay=0.1, jitter=0.0,
                        sleep=slept.append)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise IOError("transient")
            return "ok"

        assert p.call(flaky, component="test") == "ok"
        assert calls["n"] == 3
        assert slept == [0.1, 0.2]
        assert _counter_value("bigdl_reliability_retries_total",
                              component="test") == 2

    def test_budget_exhausted_reraises_last_error(self):
        p = RetryPolicy(max_attempts=3, base_delay=0.01, jitter=0.0,
                        sleep=lambda s: None)
        calls = {"n": 0}

        def always():
            calls["n"] += 1
            raise ValueError("permanent")

        with pytest.raises(ValueError, match="permanent"):
            p.call(always)
        assert calls["n"] == 3             # attempts, not retries

    def test_deadline_cuts_retries_short(self):
        t = {"now": 0.0}
        d = Deadline(0.15, clock=lambda: t["now"])
        p = RetryPolicy(max_attempts=10, base_delay=0.1, jitter=0.0,
                        sleep=lambda s: t.__setitem__("now",
                                                      t["now"] + s))

        def always():
            raise IOError("down")

        # retry delays would sum past the deadline: raises the op error
        # (not DeadlineExceeded) once sleeping further would be pointless
        with pytest.raises(IOError):
            p.call(always, deadline=d)
        assert t["now"] <= 0.15


class TestDeadline:
    def test_expiry_on_fake_clock(self):
        t = {"now": 100.0}
        d = Deadline(0.5, clock=lambda: t["now"])
        assert not d.expired()
        assert 0.4 < d.remaining() <= 0.5
        t["now"] += 1.0
        assert d.expired()
        with pytest.raises(rel.DeadlineExceeded):
            d.check("unit test")
        assert _counter_value(
            "bigdl_reliability_deadline_expired_total") == 1

    def test_header_roundtrip(self):
        d = Deadline(1.0)
        ms = int(d.to_header())
        assert 0 < ms <= 1000
        d2 = Deadline.from_header(str(ms))
        assert d2 is not None and d2.remaining() <= 1.0
        assert Deadline.from_header(None) is None
        assert Deadline.from_header("garbage") is None


class TestCircuitBreaker:
    def test_state_machine(self):
        t = {"now": 0.0}
        br = CircuitBreaker("t", failure_threshold=3, reset_timeout=10.0,
                            clock=lambda: t["now"])
        assert br.state == "closed"
        for _ in range(2):
            br.record_failure()
        assert br.state == "closed"        # below threshold
        br.record_failure()
        assert br.state == "open"
        assert not br.allow()
        with pytest.raises(rel.CircuitOpenError):
            br.call(lambda: "never")
        t["now"] = 10.0                    # reset timeout elapses
        assert br.state == "half_open"
        assert br.allow()
        br.record_failure()                # probe fails -> reopen
        assert br.state == "open"
        t["now"] = 20.0
        assert br.call(lambda: "probe") == "probe"   # probe succeeds
        assert br.state == "closed"
        # trips and recoveries are visible on /metrics
        assert _counter_value(
            "bigdl_reliability_breaker_transitions_total",
            name="t", state="open") == 2
        assert _counter_value(
            "bigdl_reliability_breaker_transitions_total",
            name="t", state="closed") == 1


# ---------------------------------------------------------------------------
# fault injection registry
# ---------------------------------------------------------------------------

class TestFaultInjection:
    def test_noop_without_plan(self):
        assert rel.inject("checkpoint.write") is None
        assert rel.armed_sites() == []

    def test_plan_fires_deterministically_and_counts(self):
        plan = rel.FaultPlan(seed=3)
        plan.add("optimizer.step", "raise", after=1, times=1)
        rel.set_plan(plan)
        assert rel.inject("optimizer.step") is None     # after=1 skips
        with pytest.raises(rel.InjectedFault):
            rel.inject("optimizer.step")
        assert rel.inject("optimizer.step") is None     # times=1 spent
        assert plan.fired == [("optimizer.step", "raise")]
        assert _counter_value(
            "bigdl_reliability_injected_faults_total",
            site="optimizer.step", action="raise") == 1

    def test_glob_sites_and_corrupt_action(self):
        plan = rel.FaultPlan()
        plan.add("checkpoint.*", "corrupt", times=2)
        rel.set_plan(plan)
        assert rel.inject("checkpoint.write.arrays") == "corrupt"
        assert rel.inject("checkpoint.commit") == "corrupt"
        assert rel.inject("checkpoint.load") is None
        assert rel.armed_sites() == ["checkpoint.*"]

    def test_delay_action_sleeps(self):
        plan = rel.FaultPlan()
        plan.add("serving.batch", "delay", delay=0.02, times=1)
        rel.set_plan(plan)
        t0 = time.perf_counter()
        assert rel.inject("serving.batch") == "delay"
        assert time.perf_counter() - t0 >= 0.015

    def test_randomize_is_reproducible(self):
        sites_a = rel.FaultPlan(seed=5).randomize(6).sites()
        sites_b = rel.FaultPlan(seed=5).randomize(6).sites()
        assert sites_a == sites_b


# ---------------------------------------------------------------------------
# atomic checkpoints
# ---------------------------------------------------------------------------

def _tree():
    return {"w": np.arange(12, dtype=np.float32).reshape(3, 4), "step": 7}


class TestAtomicCheckpoint:
    def test_roundtrip_and_checksums(self, tmp_path):
        p = str(tmp_path / "optim.1.1")
        ckpt.save_checkpoint(p, _tree())
        assert ckpt.verify_checkpoint(p)
        tree, _ = ckpt.load_checkpoint(p, to_jax=False)
        np.testing.assert_array_equal(tree["w"], _tree()["w"])
        with open(os.path.join(p, "manifest.json")) as f:
            manifest = json.load(f)
        assert "arrays.safetensors" in manifest["files"]
        assert manifest["files"]["arrays.safetensors"]["sha256"]

    def test_writer_killed_between_arrays_and_manifest(self, tmp_path):
        """Satellite regression: the seed wrote arrays then manifest into
        the LIVE dir — a crash between the two left a half-checkpoint
        recovery would happily load. Now the partial write stays in a
        .tmp sibling: never loadable, never visible to latest()."""
        root = str(tmp_path)
        p = os.path.join(root, "optim.1.1")
        plan = rel.FaultPlan()
        plan.add("checkpoint.write.manifest", "raise", times=1)
        rel.set_plan(plan)
        with pytest.raises(rel.InjectedFault):
            ckpt.save_checkpoint(p, _tree())
        rel.set_plan(None)
        assert not os.path.exists(p)            # nothing published
        assert ckpt.latest(root) is None        # nothing to resume from
        with pytest.raises(Exception):
            ckpt.load_checkpoint(p)
        # and a crash during commit also publishes nothing
        plan = rel.FaultPlan()
        plan.add("checkpoint.commit", "raise", times=1)
        rel.set_plan(plan)
        with pytest.raises(rel.InjectedFault):
            ckpt.save_checkpoint(p, _tree())
        assert ckpt.latest(root) is None

    def test_injected_corruption_is_caught_and_quarantined(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint(os.path.join(root, "optim.1.1"), _tree())
        plan = rel.FaultPlan()
        plan.add("checkpoint.write.arrays", "corrupt", times=1)
        rel.set_plan(plan)
        p = os.path.join(root, "optim.1.2")
        ckpt.save_checkpoint(p, _tree())        # corrupted in flight
        rel.set_plan(None)
        assert not ckpt.verify_checkpoint(p)
        with pytest.raises(ckpt.CheckpointCorruptError):
            ckpt.load_checkpoint(p)
        # latest() must skip + quarantine the torn newest checkpoint and
        # hand recovery the older healthy one, never the garbage
        assert ckpt.latest(root) == "1.1"
        assert not os.path.exists(p)            # moved aside
        assert any(".corrupt-" in n for n in os.listdir(root))
        assert _counter_value(
            "bigdl_reliability_checkpoints_quarantined_total") == 1

    def test_overwrite_replaces_atomically(self, tmp_path):
        p = str(tmp_path / "optim.1.1")
        ckpt.save_checkpoint(p, _tree())
        ckpt.save_checkpoint(p, {"w": np.zeros(2, np.float32)})
        tree, _ = ckpt.load_checkpoint(p, to_jax=False)
        assert tree["w"].shape == (2,)
        assert ckpt.verify_checkpoint(p)

    def test_retention_prunes_old_tags_and_tmp_orphans(self, tmp_path):
        root = str(tmp_path)
        for ne in range(1, 6):
            ckpt.save_checkpoint(os.path.join(root, f"optim.1.{ne}"),
                                 _tree())
            ckpt.save_checkpoint(os.path.join(root, f"model.1.{ne}"),
                                 _tree())
        os.makedirs(os.path.join(root, "optim.1.9.tmp-123-dead"))
        pruned = ckpt.prune_checkpoints(root, keep=2)
        assert pruned == ["1.1", "1.2", "1.3"]
        left = sorted(os.listdir(root))
        assert left == ["model.1.4", "model.1.5", "optim.1.4",
                        "optim.1.5"]

    def test_legacy_manifest_without_checksums_still_loads(self, tmp_path):
        p = str(tmp_path / "legacy")
        ckpt.save_checkpoint(p, _tree())
        with open(os.path.join(p, "manifest.json")) as f:
            manifest = json.load(f)
        del manifest["files"]                   # PR-1 layout
        with open(os.path.join(p, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        tree, _ = ckpt.load_checkpoint(p, to_jax=False)
        assert tree["step"] == 7
        assert ckpt.verify_checkpoint(p)


# ---------------------------------------------------------------------------
# recovery semantics: training
# ---------------------------------------------------------------------------

def _training_setup(tmp_path, epochs=4):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.feature.dataset import LocalDataSet
    from bigdl_tpu.nn.module import set_seed
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    set_seed(0)
    model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
             .add(nn.Linear(16, 4)).add(nn.LogSoftMax()))
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    t = (rs.randint(0, 4, 64) + 1).astype(np.int32)
    opt = LocalOptimizer(model, LocalDataSet(x, t, shuffle=False),
                         nn.ClassNLLCriterion(), batch_size=16,
                         end_trigger=Trigger.max_epoch(epochs))
    opt.set_checkpoint(str(tmp_path), Trigger.every_epoch())
    return opt, x, t


class TestPreemptionRoundTrip:
    def test_sigterm_checkpoints_then_exits_and_resumes_exactly(
            self, tmp_path):
        import jax
        opt, x, t = _training_setup(tmp_path)
        hits = {"n": 0}
        orig = opt._check_preemption

        def hook(params, states, opt_state, state):
            hits["n"] += 1
            if hits["n"] == 5:
                # what the installed SIGTERM handler does: set the flag
                opt._preempt_requested = True
            return orig(params, states, opt_state, state)

        opt._check_preemption = hook
        with pytest.raises(rel.TrainingPreempted):
            opt.optimize()
        saved_neval = opt.state["neval"]
        assert _counter_value("bigdl_reliability_preemptions_total") == 1
        tag = ckpt.latest(str(tmp_path), paired_prefix="model.")
        assert tag is not None and tag.endswith(str(saved_neval))
        saved_params, _ = ckpt.load_checkpoint(
            str(tmp_path / f"model.{tag}"), to_jax=False)

        # fresh process: auto-resume at the exact saved iteration with
        # bit-identical params
        opt2, _, _ = _training_setup(tmp_path)
        resumed = {}
        orig_once = opt2._optimize_once

        def capture():
            resumed["neval"] = opt2.state["neval"]
            resumed["params"] = [
                np.asarray(p) for p in jax.tree_util.tree_leaves(
                    opt2.model.parameters_dict())]
            return orig_once()

        opt2._optimize_once = capture
        opt2.optimize()
        assert resumed["neval"] == saved_neval
        for a, b in zip(resumed["params"],
                        jax.tree_util.tree_leaves(saved_params["params"])):
            np.testing.assert_array_equal(a, b)   # bit-identical
        assert opt2.state["epoch"] > 4            # and training finished

    def test_signal_handler_installed_and_restored(self, tmp_path):
        import signal as sig
        opt, _, _ = _training_setup(tmp_path, epochs=1)
        seen = {}
        orig_once = opt._optimize_once

        def capture():
            seen["term"] = sig.getsignal(sig.SIGTERM)
            return orig_once()

        opt._optimize_once = capture
        before = sig.getsignal(sig.SIGTERM)
        opt.optimize()
        assert seen["term"] is not before      # installed during the run
        assert sig.getsignal(sig.SIGTERM) is before   # restored after

    def test_mid_iteration_crash_recovers_from_checkpoint(self, tmp_path):
        """Acceptance: injected mid-iteration crash + retry budget →
        training recovers automatically from the newest checkpoint."""
        opt, x, t = _training_setup(tmp_path)
        opt.set_max_retry(2)
        plan = rel.FaultPlan()
        plan.add("optimizer.step", "raise", after=6, times=1)
        rel.set_plan(plan)
        trained = opt.optimize()
        rel.set_plan(None)
        assert plan.fired == [("optimizer.step", "raise")]
        assert opt.state["epoch"] > 4
        assert _counter_value("bigdl_reliability_retries_total",
                              component="optimizer") == 1
        y = np.asarray(trained.evaluate().forward(x[:4]))
        assert y.shape == (4, 4)

    def test_corrupt_newest_checkpoint_quarantined_on_recovery(
            self, tmp_path):
        """Corrupt-checkpoint quarantine: recovery must skip a torn
        newest checkpoint and restore the older valid one."""
        opt, x, t = _training_setup(tmp_path)
        opt.set_max_retry(2)
        plan = rel.FaultPlan()
        # corrupt the arrays of one optimizer checkpoint write, then
        # crash a later step so recovery has to scan the dir
        plan.add("checkpoint.write.arrays", "corrupt", after=2, times=1)
        plan.add("optimizer.step", "raise", after=10, times=1)
        rel.set_plan(plan)
        opt.optimize()
        rel.set_plan(None)
        assert ("optimizer.step", "raise") in plan.fired
        assert ("checkpoint.write.arrays", "corrupt") in plan.fired
        assert opt.state["epoch"] > 4
        names = os.listdir(tmp_path)
        assert any(".corrupt-" in n for n in names)


# ---------------------------------------------------------------------------
# serving backpressure
# ---------------------------------------------------------------------------

def _post(addr, path, obj, headers=None):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    body = json.dumps(obj)
    conn.request("POST", path, body=body,
                 headers={"Content-Type": "application/json",
                          **(headers or {})})
    r = conn.getresponse()
    out = (r.status, dict(r.getheaders()), json.loads(r.read() or b"{}"))
    conn.close()
    return out


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    conn.request("GET", path)
    r = conn.getresponse()
    out = (r.status, json.loads(r.read() or b"{}"))
    conn.close()
    return out


class TestFrontendBackpressure:
    def test_timeout_evicts_pending_entry(self):
        """Satellite regression: a timed-out /predict used to leave its
        event entry behind, so the late result accumulated forever."""
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        fe = ServingFrontend(stream_name="rel_evict",
                             result_timeout=0.05).start()
        try:
            # no serving job attached: every request times out
            status, _, _ = _post(fe.address, "/predict",
                                 {"inputs": {"x": [[1.0]]}})
            assert status == 504
            with fe._lock:
                assert fe._events == {}         # evicted on timeout
                assert fe._results == {}
            # a late result for the dead uri must be dropped, not stored
            fe._out._cache.clear()
        finally:
            fe.stop()

    def test_overload_sheds_503_with_retry_after(self):
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        fe = ServingFrontend(stream_name="rel_shed", result_timeout=0.5,
                             max_pending=0).start()   # everything sheds
        try:
            status, headers, body = _post(fe.address, "/predict",
                                          {"inputs": {"x": [[1.0]]}})
            assert status == 503
            assert headers.get("Retry-After") == "1"
            assert "overloaded" in body["error"]
            assert fe.shed == 1
            assert _counter_value("bigdl_reliability_shed_total",
                                  component="serving_frontend") == 1
        finally:
            fe.stop()

    def test_healthz_and_drain(self):
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        fe = ServingFrontend(stream_name="rel_hz",
                             result_timeout=0.2).start()
        try:
            status, body = _get(fe.address, "/healthz")
            assert status == 200 and body["status"] == "ok"
            assert any(k.startswith("serving_frontend:")
                       for k in body["checks"])
        finally:
            fe.stop()
        # stop() unregisters the instance's health check
        assert not any(k.startswith("serving_frontend:")
                       for k in rel.health_checks())

    def test_draining_frontend_sheds_new_work(self):
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        fe = ServingFrontend(stream_name="rel_drain",
                             result_timeout=0.2).start()
        try:
            fe._draining.set()
            status, headers, body = _post(fe.address, "/predict",
                                          {"inputs": {"x": [[1.0]]}})
            assert status == 503 and "draining" in body["error"]
        finally:
            fe.stop()

    def test_request_deadline_header_caps_wait(self):
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        fe = ServingFrontend(stream_name="rel_dl",
                             result_timeout=30.0).start()
        try:
            t0 = time.perf_counter()
            status, _, _ = _post(fe.address, "/predict",
                                 {"inputs": {"x": [[1.0]]}},
                                 headers={rel.DEADLINE_HEADER: "100"})
            took = time.perf_counter() - t0
            assert status == 504          # deadline, not the 30s timeout
            assert took < 5.0
        finally:
            fe.stop()

    def test_end_to_end_with_injected_backend_faults(self):
        """A full predict round-trip with delay faults armed on the
        queue backend: slower, but every request still completes."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu.serving.cluster_serving import ClusterServing
        from bigdl_tpu.serving.http_frontend import ServingFrontend
        from bigdl_tpu.serving.inference_model import InferenceModel

        model = nn.Sequential().add(nn.Linear(4, 2))
        im = InferenceModel().load_bigdl(model=model)
        plan = rel.FaultPlan(seed=11)
        plan.add("serving.backend.*", "delay", delay=0.01, times=4)
        rel.set_plan(plan)
        job = ClusterServing(im, stream_name="rel_e2e",
                             batch_size=4, batch_timeout=0.01).start()
        fe = ServingFrontend(stream_name="rel_e2e",
                             result_timeout=20.0).start()
        try:
            status, _, body = _post(
                fe.address, "/predict",
                {"inputs": {"x": [[1.0, 2.0, 3.0, 4.0]]}})
            assert status == 200
            assert np.asarray(body["result"]).shape == (1, 2)
            assert plan.fired   # faults really fired along the way
        finally:
            rel.set_plan(None)
            fe.stop()
            job.stop()


class TestRedisReconnect:
    def test_reconnect_with_backoff_behind_breaker(self, monkeypatch):
        """Acceptance: redis disconnect recovers automatically. The
        redis client lib is not in the image, so a fake module stands in
        — first N ops raise ConnectionError, then the backend must have
        reconnected and succeeded, counting its retries."""
        state = {"clients": 0, "fail_ops": 2}

        class FakeRedis:
            def __init__(self, host=None, port=None):
                state["clients"] += 1

            def ping(self):
                return True

            def rpush(self, stream, payload):
                if state["fail_ops"] > 0:
                    state["fail_ops"] -= 1
                    raise ConnectionError("connection reset")
                state.setdefault("pushed", []).append(payload)

            def blpop(self, streams, timeout=1):
                pushed = state.get("pushed", [])
                return ("q", pushed.pop(0)) if pushed else None

        fake = types.ModuleType("redis")
        fake.Redis = FakeRedis
        monkeypatch.setitem(sys.modules, "redis", fake)

        from bigdl_tpu.serving.cluster_serving import _RedisBackend
        be = _RedisBackend(
            "localhost", 6379,
            retry=RetryPolicy(max_attempts=5, base_delay=0.001,
                              jitter=0.0))
        be.push("q", b"payload")
        assert state["clients"] >= 3         # initial + 2 reconnects
        assert be.reconnects() == 2
        assert be.pop("q", timeout=0.1) == b"payload"
        assert be._breaker.state == "closed"
        assert _counter_value("bigdl_reliability_retries_total",
                              component="redis_backend") == 2

    def test_breaker_opens_when_queue_stays_down(self, monkeypatch):
        class DeadRedis:
            def __init__(self, host=None, port=None):
                pass

            def ping(self):
                return True

            def rpush(self, *a):
                raise ConnectionError("still down")

        fake = types.ModuleType("redis")
        fake.Redis = DeadRedis
        monkeypatch.setitem(sys.modules, "redis", fake)
        from bigdl_tpu.serving.cluster_serving import _RedisBackend
        be = _RedisBackend(
            "localhost", 6379,
            retry=RetryPolicy(max_attempts=2, base_delay=0.001,
                              jitter=0.0),
            breaker=CircuitBreaker("test_redis", failure_threshold=2,
                                   reset_timeout=60.0))
        for _ in range(2):
            with pytest.raises(ConnectionError):
                be.push("q", b"x")
        # breaker open: callers now fail fast without touching the socket
        with pytest.raises(rel.CircuitOpenError):
            be.push("q", b"x")


class TestLLMWorkerBackpressure:
    class _StubServer:
        """submit/queue surface of LLMServer without a model."""

        def __init__(self):
            self._queue = __import__("queue").Queue()
            self._thread = threading.Thread(target=lambda: time.sleep(30),
                                            daemon=True)
            self._thread.start()
            self._draining = threading.Event()
            self.steps = 0
            self.eos_token_id = None
            self.overloaded = False

        def submit(self, ids, max_new_tokens=32):
            if self.overloaded:
                raise rel.OverloadError("request queue full (2 waiting)")
            from bigdl_tpu.llm.serving import Request
            req = Request(np.asarray(ids, np.int32), max_new_tokens)
            req.tokens = [1, 2, 3]
            req.done.set()
            return req

    def test_queue_full_sheds_503_with_retry_after(self):
        from bigdl_tpu.llm.worker import LLMWorker
        srv = self._StubServer()
        worker = LLMWorker(srv).start()
        try:
            status, _, body = _post(worker.address, "/worker_generate",
                                    {"prompt_ids": [1, 2]})
            assert status == 200 and body["output_ids"] == [1, 2, 3]
            srv.overloaded = True
            status, headers, body = _post(worker.address,
                                          "/worker_generate",
                                          {"prompt_ids": [1, 2]})
            assert status == 503
            assert headers.get("Retry-After") == "1"
            assert "queue full" in body["error"]
        finally:
            worker.stop()

    def test_healthz_reports_engine_liveness(self):
        from bigdl_tpu.llm.worker import LLMWorker
        srv = self._StubServer()
        worker = LLMWorker(srv).start()
        try:
            status, body = _get(worker.address, "/healthz")
            assert status == 200
            assert body["engine_alive"] is True
            srv._draining.set()
            status, body = _get(worker.address, "/healthz")
            assert status == 503 and body["status"] == "draining"
        finally:
            worker.stop()

    def test_prefill_failure_releases_budget_and_fails_request(self):
        """Review regression: a raising prefill must restore the page
        budget (the resilient engine loop would otherwise shrink the
        admission pool forever) and unblock the client with the error
        instead of letting it hang to timeout."""
        from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
        from bigdl_tpu.llm.serving import LLMServer
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(vocab=64),
                                             seed=0, max_cache_len=64)
        srv = LLMServer(model, max_batch=1, max_seq_len=32)
        before_budget = srv._budget_avail
        before_pages = len(srv._free)

        def boom(i, req, adm):
            raise RuntimeError("prefill exploded")

        srv._prefill_ragged = boom
        req = srv.submit([1, 2, 3], max_new_tokens=2)
        with pytest.raises(RuntimeError, match="prefill exploded"):
            srv._admit()           # engine loop not started: call direct
        assert srv._budget_avail == before_budget
        assert len(srv._free) == before_pages
        assert srv._slots[0] is None
        with pytest.raises(RuntimeError, match="prefill exploded"):
            req.get(timeout=0.1)   # failed fast, not hung

    def test_llm_server_bounded_queue_and_drain(self):
        """Real LLMServer admission: with max_queue=1 and the engine
        loop not started, the second waiting submit is shed; draining
        rejects all new work."""
        from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(vocab=64),
                                             seed=0, max_cache_len=64)
        from bigdl_tpu.llm.serving import LLMServer
        srv = LLMServer(model, max_batch=1, max_seq_len=32, max_queue=1)
        srv.submit([1, 2, 3], max_new_tokens=2)     # fills the queue
        with pytest.raises(rel.OverloadError, match="queue full"):
            srv.submit([1, 2, 3], max_new_tokens=2)
        assert _counter_value("bigdl_reliability_shed_total",
                              component="llm_server") == 1
        srv._draining.set()
        srv._queue.get_nowait()
        with pytest.raises(rel.OverloadError, match="draining"):
            srv.submit([1, 2, 3], max_new_tokens=2)


# ---------------------------------------------------------------------------
# disabled mode: structurally absent, zero overhead
# ---------------------------------------------------------------------------

class TestDisabledMode:
    def test_disabled_is_structurally_absent(self, tmp_path):
        conf.set("bigdl.reliability.enabled", "false")
        try:
            assert not rel.enabled()
            # no plan can arm
            with pytest.raises(RuntimeError):
                rel.set_plan(rel.FaultPlan())
            assert rel.armed_sites() == []
            # inject is a pure no-op
            assert rel.inject("checkpoint.write") is None
            # health registrations are ignored
            rel.register_health("x", lambda: True)
            assert rel.health_checks() == {}
            # no signal handlers installed during training
            import signal as sig
            before = sig.getsignal(sig.SIGTERM)
            opt, x, t = _training_setup(tmp_path, epochs=1)
            seen = {}
            orig_once = opt._optimize_once

            def capture():
                seen["term"] = sig.getsignal(sig.SIGTERM)
                return orig_once()

            opt._optimize_once = capture
            opt.optimize()
            assert seen["term"] is before
            # checkpoint layout unchanged and loadable by the PR-1
            # reader (same two files + sidecar; extra manifest keys only)
            tag = ckpt.latest(str(tmp_path), paired_prefix="model.")
            assert tag is not None
            model_dir = str(tmp_path / f"model.{tag}")
            assert sorted(os.listdir(model_dir)) == [
                "arrays.safetensors", "manifest.json", "structure.pkl"]
            tree, _ = ckpt.load_checkpoint(model_dir, to_jax=False,
                                           verify=False)   # PR-1 path
            assert "params" in tree
            # zero reliability counters were minted along the way
            rendered = obs.render()
            assert "bigdl_reliability_" not in rendered
        finally:
            conf.unset("bigdl.reliability.enabled")
            assert rel.enabled()    # unset() restores the default=true

    def test_disabled_policies_work_but_mint_no_counters(self):
        """Review regression: policy objects keep functioning when the
        layer is disabled, but must mint ZERO bigdl_reliability_* series
        (the retry paths in the optimizer/serving loops run regardless)."""
        conf.set("bigdl.reliability.enabled", "false")
        try:
            p = RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0,
                            sleep=lambda s: None)
            calls = {"n": 0}

            def flaky():
                calls["n"] += 1
                if calls["n"] < 2:
                    raise IOError("transient")
                return "ok"

            assert p.call(flaky, component="gated") == "ok"
            br = CircuitBreaker("gated", failure_threshold=1)
            br.record_failure()
            assert br.state == "open"      # machine still works
            assert "bigdl_reliability_" not in obs.render()
        finally:
            conf.unset("bigdl.reliability.enabled")

    def test_conf_toggle_roundtrip(self):
        conf.set("bigdl.reliability.enabled", "false")
        assert not rel.enabled()
        conf.set("bigdl.reliability.enabled", "true")
        assert rel.enabled()
        conf.unset("bigdl.reliability.enabled")
        assert rel.enabled()

    def test_retry_knobs_come_from_conf(self):
        conf.set("bigdl.reliability.retry.max.attempts", "7")
        conf.set("bigdl.reliability.retry.base.delay", "0.5")
        try:
            p = RetryPolicy(jitter=0.0)
            assert p.max_attempts == 7
            assert list(p.delays())[0] == 0.5
        finally:
            conf.unset("bigdl.reliability.retry.max.attempts")
            conf.unset("bigdl.reliability.retry.base.delay")


# ---------------------------------------------------------------------------
# chaos (seeded randomized injection; slow => outside the tier-1 gate)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 7, 42])
def test_chaos_lenet_converges_like_clean_run(seed):
    """N seeded kill/corrupt/delay events over training + checkpointing:
    the run must recover automatically and land on the SAME final loss
    as an uninjected run (tools/chaos_check.py)."""
    from tools.chaos_check import run_chaos
    out = run_chaos(seed=seed, events=4, smoke=True)
    assert out["match"]
    assert out["events_fired"]        # the plan really fired something


class TestCheckpointKeepConf:
    def test_training_prunes_to_keep(self, tmp_path):
        conf.set("bigdl.checkpoint.keep", "2")
        try:
            opt, _, _ = _training_setup(tmp_path, epochs=4)
            opt.optimize()
            tags = ckpt.list_checkpoint_tags(str(tmp_path))
            assert len(tags) == 2          # retention enforced
            # and the survivors are the newest pair
            assert ckpt.latest(str(tmp_path),
                               paired_prefix="model.") == tags[-1]
        finally:
            conf.unset("bigdl.checkpoint.keep")
