"""SLO-class priority scheduling + lossless preemption (ISSUE 17):
class-model units, class-ordered admission on the live engine, the
headline preempt→fence-release→resume parity run (pipeline depth 4,
parked kvtier fetch, shared radix prefix), and the disabled-mode
structural-absence contract for ``bigdl.llm.priority.enabled``.

Engine tests run the tier migrator in SYNCHRONOUS mode
(``bigdl.llm.kvtier.sync``) — a host-arena hit still parks the
admission in ``_fetch_wait`` for a pass (the job just lands inline),
so the parked-fetch path is exercised without racy sleeps."""

import threading

import numpy as np
import pytest

from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import (CLASS_RETRY_WEIGHTS, PRIORITY_CLASSES,
                                   LLMServer, _PriorityScheduler,
                                   normalize_priority)
from bigdl_tpu.utils.conf import conf

pytestmark = pytest.mark.priority

PAGE = 8


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=128)


@pytest.fixture()
def sync_tier():
    """Inline migration for deterministic, sleep-free engine tests."""
    conf.set("bigdl.llm.kvtier.sync", "true")
    yield
    conf.unset("bigdl.llm.kvtier.sync")


def _generate(model, p, n):
    return model.generate(np.asarray(p)[None], max_new_tokens=n)[0, len(p):]


class _Stub:
    """Minimal request stand-in for scheduler units (the scheduler only
    reads .priority/.done/.resume_ids)."""

    def __init__(self, priority, resumed=False):
        self.priority = priority
        self.done = threading.Event()
        self.resume_ids = np.zeros(1, np.int32) if resumed else None


# ---------------------------------------------------------------------------
# class model: normalization, retry weights, heap ordering
# ---------------------------------------------------------------------------

class TestClassModel:
    def test_normalize_degrades_never_fails(self):
        # header values are client-controlled: unknown/missing classes
        # must degrade to "standard", never raise
        assert normalize_priority(None) == "standard"
        assert normalize_priority("interactive") == "interactive"
        assert normalize_priority("  BATCH ") == "batch"
        assert normalize_priority("Standard") == "standard"
        assert normalize_priority("p99-or-bust") == "standard"
        assert normalize_priority(7) == "standard"

    def test_retry_weights_order_backoff_by_class(self):
        # batch clients must back off harder than interactive under the
        # same backlog (the class-weighted Retry-After satellite)
        assert (CLASS_RETRY_WEIGHTS["interactive"]
                < CLASS_RETRY_WEIGHTS["standard"]
                < CLASS_RETRY_WEIGHTS["batch"])
        assert set(CLASS_RETRY_WEIGHTS) == set(PRIORITY_CLASSES)

    def test_scheduler_class_order_fifo_within_class(self):
        sched = _PriorityScheduler()
        b1, i1, s1, i2 = (_Stub("batch"), _Stub("interactive"),
                          _Stub("standard"), _Stub("interactive"))
        for r in (b1, i1, s1, i2):
            sched.push(r)
        order = []
        while len(sched):
            order.append(sched.pop_entry()[2])
        assert order == [i1, i2, s1, b1]

    def test_scheduler_reparked_head_keeps_its_place(self):
        sched = _PriorityScheduler()
        a, b = _Stub("standard"), _Stub("standard")
        sched.push(a)
        sched.push(b)
        ent = sched.pop_entry()          # budget-blocked head...
        sched.push_entry(ent)            # ...re-parks at the FRONT
        assert sched.pop_entry()[2] is a
        assert sched.pop_entry()[2] is b

    def test_scheduler_depths_and_parked(self):
        sched = _PriorityScheduler()
        sched.push(_Stub("interactive"))
        sched.push(_Stub("batch"))
        victim = _Stub("batch", resumed=True)   # preempted, awaiting resume
        sched.push(victim)
        finished = _Stub("standard")
        finished.done.set()
        sched.push(finished)
        assert sched.depths() == {"interactive": 1, "standard": 0,
                                  "batch": 2}
        assert sched.parked() == 1
        assert sched.live() == 3
        assert sched.best_rank() == 0


# ---------------------------------------------------------------------------
# loadgen: --priority-mix plumbing (pure units)
# ---------------------------------------------------------------------------

class TestLoadgenMix:
    def test_parse_and_assign_deterministic(self):
        from tools.loadgen import assign_classes, parse_priority_mix

        mix = parse_priority_mix("interactive:1,batch:2")
        assert mix == [("interactive", 1), ("batch", 2)]
        classes = assign_classes(6, mix)
        assert classes == ["interactive", "batch", "batch"] * 2
        assert assign_classes(6, mix) == classes   # stable across calls

    def test_parse_rejects_bad_specs(self):
        from tools.loadgen import parse_priority_mix

        with pytest.raises(ValueError):
            parse_priority_mix("interactive:0,batch:0")
        with pytest.raises(ValueError):
            parse_priority_mix("warp-speed:1")
        with pytest.raises(ValueError):
            parse_priority_mix("")


# ---------------------------------------------------------------------------
# engine: class-ordered admission
# ---------------------------------------------------------------------------

class TestClassOrderedAdmission:
    def test_backlog_served_in_class_order(self, model):
        """One slot, one long-running interactive request, then a
        batch→standard→interactive backlog submitted in REVERSE class
        order: first-token stamps must come out interactive, standard,
        batch — the heap, not arrival order, decides."""
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, 250, 6 + j).astype(np.int32)
                   for j in range(4)]
        srv = LLMServer(model, max_batch=1, max_seq_len=64,
                        page_size=PAGE, num_pages=12, kvcache=True,
                        priority=True).start()
        try:
            # rank-0 occupant: never a preemption victim for a rank-0
            # waiter (preemption needs a strictly better class)
            head = srv.submit(prompts[0], max_new_tokens=24,
                              priority="interactive")
            while not head.tokens and not head.done.is_set():
                pass
            rb = srv.submit(prompts[1], max_new_tokens=2,
                            priority="batch")
            rstd = srv.submit(prompts[2], max_new_tokens=2)  # standard
            ri = srv.submit(prompts[3], max_new_tokens=2,
                            priority="interactive")
            for r in (head, rb, rstd, ri):
                r.get(timeout=600)
            assert srv.preemptions_total == 0
        finally:
            srv.stop()
        assert ri.t_first_token < rstd.t_first_token < rb.t_first_token


# ---------------------------------------------------------------------------
# engine: the headline lossless-preemption run
# ---------------------------------------------------------------------------

class TestPreemptResume:
    def test_preempt_resume_parity_pipeline4_parked_fetch(self, model,
                                                          sync_tier):
        """The ISSUE 17 acceptance run: pipeline depth 4, batch decodes
        whose shared radix prefix re-admits through a parked kvtier
        fetch, an interactive burst that preempts in-flight victims —
        every output (victims included) must match generate() exactly,
        every preemption must resume, and the page/pin ledgers and
        host arena must come back idle."""
        rs = np.random.RandomState(11)
        shared = rs.randint(0, 250, 16).astype(np.int32)
        batch_prompts = [np.concatenate(
            [shared, rs.randint(0, 250, 2 + j).astype(np.int32)])
            for j in range(3)]
        fills = [rs.randint(0, 250, 24).astype(np.int32)
                 for _ in range(3)]
        inter_prompts = [rs.randint(0, 250, 6 + j).astype(np.int32)
                         for j in range(2)]
        n_batch, n_inter = 20, 3
        want_b = [_generate(model, p, n_batch) for p in batch_prompts]
        want_i = [_generate(model, p, n_inter) for p in inter_prompts]
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=PAGE, num_pages=12, kvcache=True,
                        kvtier=True, host_pages=64, pipeline_depth=4,
                        priority=True).start()
        try:
            # pass 1: seed the shared-prefix chains, then evict them to
            # the host arena with distinct fill chains — the storm's
            # batch admissions must come back through a tier fetch
            for j, p in enumerate(batch_prompts):
                got = srv.submit(p, max_new_tokens=2,
                                 priority="batch").get(timeout=600)
                np.testing.assert_array_equal(np.asarray(got),
                                              want_b[j][:2])
            for f in fills:
                srv.submit(f, max_new_tokens=2).get(timeout=600)
            # storm: saturate both slots with long batch decodes...
            rb = [srv.submit(p, max_new_tokens=n_batch, priority="BATCH")
                  for p in batch_prompts]   # header casing is client-set
            deadline = [r for r in rb]
            while sum(1 for r in deadline if r.tokens) < 2:
                if all(r.done.is_set() for r in deadline):
                    break
                pass
            # ...then burst interactive: no free slot, strictly better
            # class → lossless preemption of an in-flight batch decode
            ri = [srv.submit(p, max_new_tokens=n_inter,
                             priority="interactive")
                  for p in inter_prompts]
            got_b = [r.get(timeout=600) for r in rb]
            got_i = [r.get(timeout=600) for r in ri]
            preempts = srv.preemptions_total
            resumes = srv.preempt_resumes_total
            fetches = srv._tier.fetches
            inflight = srv._tier.migrator.inflight()
            parked = srv.preempt_parked
            depths = srv.class_depths()
            leftover = srv._parked
            st = srv._kv.debug_stats()
        finally:
            srv.stop()
        for j, (g, w) in enumerate(zip(got_b, want_b)):
            np.testing.assert_array_equal(
                np.asarray(g), w, err_msg=f"batch request {j} lost "
                "tokens across preemption (resume must be lossless)")
        for j, (g, w) in enumerate(zip(got_i, want_i)):
            np.testing.assert_array_equal(np.asarray(g), w,
                                          err_msg=f"interactive {j}")
        assert preempts >= 1            # the storm really preempted
        assert resumes == preempts      # every victim resumed
        assert fetches > 0              # parked-fetch path exercised
        assert parked == 0 and not leftover
        assert inflight == 0
        assert depths == {c: 0 for c in PRIORITY_CLASSES}
        # ledger/arena idle: every grant returned, nothing pinned
        assert st["pages_pinned"] == 0
        assert st["budget_avail"] == 12 - 1
        assert st["tier"]["pinned"] == 0
        assert st["tier"]["fetch_failures"] == 0

    @pytest.mark.chaos
    @pytest.mark.slow
    def test_chaos_priority_storm_keeps_parity(self):
        """tools/chaos_check.py --preempt: a priority storm under step
        delays and an injected llm.preempt fault must stay bit-identical
        to FIFO, reconcile counters with flight events, and beat the
        FIFO baseline's worst-case interactive TTFT."""
        from tools.chaos_check import run_preempt_chaos

        out = run_preempt_chaos(seed=0, smoke=True)
        assert out["match"] and out["preemptions"] >= 1
        assert out["lost_requests"] == 0


# ---------------------------------------------------------------------------
# disabled mode: structurally absent
# ---------------------------------------------------------------------------

class TestDisabledMode:
    def test_off_is_structurally_absent(self, model):
        from bigdl_tpu import observability as obs

        # the gate defaults off (gatecheck absence-test contract)
        assert conf.get_bool("bigdl.llm.priority.enabled",
                             False) is False
        before = len(obs.REGISTRY.collect())
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=PAGE, num_pages=12,
                        kvcache=True).start()
        try:
            # no scheduler, no parked-blob map, no class-key surfaces
            assert srv._sched is None
            assert srv._parked is None
            assert srv.class_depths() is None
            assert srv.preempt_parked == 0
            # priority hints are inert metadata, not a scheduler
            r1 = srv.submit(np.array([3, 1, 4, 1, 5], np.int32),
                            max_new_tokens=3, priority="interactive")
            r2 = srv.submit(np.array([2, 7, 1, 8], np.int32),
                            max_new_tokens=3, priority="batch")
            r1.get(timeout=600)
            r2.get(timeout=600)
            assert srv.preemptions_total == 0
            assert srv.preempt_resumes_total == 0
            # Retry-After depth is the plain intake depth — the class
            # weighting must not apply when the scheduler is off
            assert (srv.retry_depth("batch")
                    == srv.retry_depth("interactive")
                    == srv.retry_depth())
        finally:
            srv.stop()
        # a priority-off server must declare no new series (registry is
        # process-global, so structural absence is a DELTA)
        assert len(obs.REGISTRY.collect()) == before
