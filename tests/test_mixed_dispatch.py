"""Unified mixed prefill+decode dispatch (ISSUE 14): greedy bit-parity
vs the SPLIT engine and the plain ``generate`` golden across pipeline
depths × prefix cache on/off × chunked/unchunked admissions, COW
correctness when a chunked admission forks a radix tail while another
row live-decodes against the same prefix, the O(suffix-buckets)
compile-grid invariant over a mixed-prefix replay, the
shed-during-chunking ledger rollback, the ``llm.chunk`` fault contract
and the disabled-mode structural absence of the gate.
"""

import numpy as np
import pytest

from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer

pytestmark = pytest.mark.mixed

PAGE = 8
CHUNK = 8         # one page per chunk: every long prompt really chunks


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=128)


def _generate(model, p, n):
    return list(map(int, model.generate(
        np.asarray(p)[None], max_new_tokens=n)[0, len(p):]))


def _serve(model, prompts, lens, *, mixed, chunk_tokens=CHUNK,
           replay=1, max_seq_len=64, num_pages=None, **kw):
    srv = LLMServer(model, max_batch=2, max_seq_len=max_seq_len,
                    page_size=PAGE, mixed=mixed,
                    chunk_tokens=chunk_tokens, num_pages=num_pages,
                    **kw).start()
    try:
        for _ in range(replay):
            got = [list(map(int, r.get(timeout=600))) for r in
                   [srv.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, lens)]]
        return got, srv
    finally:
        srv.stop()


def _workload():
    """Long prompts (chunked at CHUNK=8) + short ones (unchunked),
    sharing a prefix so the cache-on matrix exercises adoption."""
    rs = np.random.RandomState(14)
    shared = rs.randint(0, 250, 20).astype(np.int32)     # 2.5 pages
    prompts = [np.concatenate(
        [shared, rs.randint(0, 250, 11 + 4 * j).astype(np.int32)])
        for j in range(3)]                               # 31/35/39 toks
    prompts.append(rs.randint(0, 250, 26).astype(np.int32))  # disjoint
    prompts.append(rs.randint(0, 250, 6).astype(np.int32))   # short
    return prompts, [4, 3, 5, 4, 4]


# goldens + the split-engine reference, computed once per cache mode
# (the split engine's own parity vs generate is PR 8's proven matrix)
_REF_CACHE = {}


def _references(model, kvcache):
    if kvcache not in _REF_CACHE:
        prompts, lens = _workload()
        golden = [_generate(model, p, n) for p, n in zip(prompts, lens)]
        split, srv = _serve(model, prompts, lens, mixed=False,
                            replay=2, kvcache=kvcache, pipeline_depth=1)
        assert srv.prefill_chunks_total == 0     # split never chunks
        _REF_CACHE[kvcache] = (golden, split)
    return _REF_CACHE[kvcache]


class TestEngineParity:
    """The acceptance matrix: unified outputs must be bit-identical to
    the split engine AND the generate golden, with chunking genuinely
    engaged (chunked) or genuinely absent (unchunked)."""

    @pytest.mark.parametrize("kvcache,depth", [
        pytest.param(True, 1), pytest.param(True, 2),
        pytest.param(True, 4), pytest.param(False, 1),
        pytest.param(False, 2), pytest.param(False, 4)])
    def test_chunked_parity_vs_split_and_golden(self, model, depth,
                                                kvcache):
        prompts, lens = _workload()
        want, split = _references(model, kvcache)
        got, srv = _serve(model, prompts, lens, mixed=True, replay=2,
                          kvcache=kvcache, pipeline_depth=depth)
        for j, (g, s, w) in enumerate(zip(got, split, want)):
            assert g == s, f"request {j}: unified vs split diverged"
            assert g == w, f"request {j}: unified vs golden diverged"
        assert srv.prefill_chunks_total > 0      # chunking engaged
        if kvcache:
            assert srv._kv.hits > 0
            # chunks fused with live decode rows actually happened
            assert srv.mixed_passes > 0

    def test_unchunked_gate_on_parity(self, model):
        """mixed ON but chunk_tokens above every suffix: the unified
        engine must route every admission through the split paths
        (zero chunks) and stay bit-identical."""
        prompts, lens = _workload()
        want, _split = _references(model, True)
        got, srv = _serve(model, prompts, lens, mixed=True,
                          chunk_tokens=64, kvcache=True,
                          pipeline_depth=2)
        assert got == want
        assert srv.prefill_chunks_total == 0
        assert srv.mixed_passes == 0

    # one facade family in tier-1 guards the hand-written NeoX mixed
    # composition (parallel residual, partial rotary); StarCoder (MQA,
    # learned wpe) rides the slow suite — same structure
    @pytest.mark.parametrize("family", [
        "gptneox", pytest.param("starcoder", marks=pytest.mark.slow)])
    def test_family_chunked_parity(self, family):
        if family == "gptneox":
            from bigdl_tpu.llm.models.gptneox import (
                GptNeoXConfig as C, GptNeoXForCausalLM as M)
        else:
            from bigdl_tpu.llm.models.starcoder import (
                StarCoderConfig as C, StarCoderForCausalLM as M)
        fam_model = M.from_config(C.tiny(), seed=0, max_cache_len=64)
        rs = np.random.RandomState(6)
        prompts = [rs.randint(0, 250, 26).astype(np.int32),
                   rs.randint(0, 250, 7).astype(np.int32)]
        lens = [4, 6]
        want = [_generate(fam_model, p, n)
                for p, n in zip(prompts, lens)]
        got, srv = _serve(fam_model, prompts, lens, mixed=True,
                          kvcache=True, pipeline_depth=2,
                          max_seq_len=48)
        assert got == want
        assert srv.prefill_chunks_total > 0

    def test_tier_prepaid_chunked_parity(self, model):
        """A host-tier admission (budget fully pre-charged at admit)
        whose landed suffix is still long chunk-DISPATCHES without
        touching the ledger again (the prepaid path) and stays
        bit-identical."""
        from bigdl_tpu.utils.conf import conf
        rs = np.random.RandomState(11)
        groups = [rs.randint(0, 250, 16).astype(np.int32)
                  for _ in range(4)]
        prompts = [np.concatenate(
            [groups[j % 4],
             rs.randint(0, 250, 10 + j % 3).astype(np.int32)])
            for j in range(8)]
        lens = [int(rs.randint(1, 5)) for _ in prompts]
        want = [_generate(model, p, n) for p, n in zip(prompts, lens)]
        conf.set("bigdl.llm.kvtier.sync", "true")
        try:
            got, srv = _serve(model, prompts, lens, mixed=True,
                              num_pages=11, kvcache=True, kvtier=True,
                              host_pages=32)
            assert srv._tier.spills > 0 and srv._tier.fetches > 0
        finally:
            conf.unset("bigdl.llm.kvtier.sync")
        assert got == want
        assert srv.prefill_chunks_total > 0

    def test_cow_fork_across_chunks_with_live_decode_row(self, model):
        """A chunked admission adopts a radix prefix whose tail page it
        must COW-fork at its FIRST chunk, while another request is
        live-decoding against the same shared pages: both streams must
        stay bit-identical to their goldens."""
        rs = np.random.RandomState(5)
        P = rs.randint(0, 250, 20).astype(np.int32)        # 2.5 pages
        B = np.concatenate([P, rs.randint(0, 250, 18).astype(np.int32)])
        want_a = _generate(model, P, 4)
        want_c = _generate(model, P, 24)
        want_b = _generate(model, B, 4)
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=PAGE, mixed=True, chunk_tokens=CHUNK,
                        kvcache=True,
                        pipeline_depth=2).start()
        try:
            # A indexes P (+ its output tail page) at EOS
            ra = srv.submit(P, max_new_tokens=4)
            assert list(map(int, ra.get(timeout=600))) == want_a
            # C adopts the chain and keeps decoding while B arrives
            rc = srv.submit(P, max_new_tokens=24)
            while len(rc.tokens) < 2:
                pass
            rb = srv.submit(B, max_new_tokens=4)
            assert list(map(int, rb.get(timeout=600))) == want_b
            assert list(map(int, rc.get(timeout=600))) == want_c
            assert srv.prefill_chunks_total > 0    # B really chunked
            assert srv._kv.hits >= 2               # C and B both hit
        finally:
            srv.stop()


class TestChunkLedger:
    def test_shed_during_chunking_rolls_back_cleanly(self, model):
        """A chunked admission that cannot charge its next chunk within
        chunk_wait is SHED: every page and ledger charge of the partial
        chain returns, the request fails retriably, and a resubmission
        after pressure clears is bit-identical to the golden."""
        rs = np.random.RandomState(7)
        a_prompt = rs.randint(0, 250, 8).astype(np.int32)
        b_prompt = rs.randint(0, 250, 32).astype(np.int32)
        want_b = _generate(model, b_prompt, 8)
        # pool of 9 budget pages: A (prompt 8 + 40 new) charges 6, so B
        # (needs 5) admits its first chunks but stalls at the decode
        # top-up and must shed while A is still decoding
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=PAGE, num_pages=10, kvcache=False,
                        mixed=True, chunk_tokens=CHUNK, chunk_wait=0.01,
                        pipeline_depth=2).start()
        try:
            ra = srv.submit(a_prompt, max_new_tokens=40)
            rb = srv.submit(b_prompt, max_new_tokens=8)
            with pytest.raises(RuntimeError) as ei:
                rb.get(timeout=600)
            assert "retriable" in str(ei.value)
            assert "starved" in str(ei.value)
            # the partial chain's budget comes back at the next
            # in-flight fence (the deferred-release contract — pages a
            # live step may still read are never freed early): poll
            # briefly, then only A's charge may remain
            import time
            deadline = time.time() + 5
            while srv._budget_avail != 3 and time.time() < deadline:
                time.sleep(0.005)
            assert srv._budget_avail == 9 - 6
            assert ra.get(timeout=600) is not None
            # pressure gone: the resubmission chunks through unharmed
            rb2 = srv.submit(b_prompt, max_new_tokens=8)
            assert list(map(int, rb2.get(timeout=600))) == want_b
        finally:
            srv.stop()
        assert srv._budget_avail == 9          # idle ledger balanced
        assert srv.pages_in_use == 0

    def test_chunk_fault_rolls_back_and_retries_identically(self, model):
        """The llm.chunk fault site: a raise between chunks frees the
        partial chain, fails the request retriably, and the resubmitted
        request is bit-identical (the chaos_check --mixed contract,
        tier-1 sized)."""
        from bigdl_tpu import reliability as rel
        rs = np.random.RandomState(9)
        prompt = rs.randint(0, 250, 30).astype(np.int32)
        want = _generate(model, prompt, 4)
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=PAGE, num_pages=24, kvcache=True,
                        mixed=True, chunk_tokens=CHUNK,
                        pipeline_depth=2).start()
        was = rel.enabled()
        if not was:
            rel.enable()
        try:
            plan = rel.FaultPlan(seed=0)
            plan.add("llm.chunk", "raise", times=1)
            rel.set_plan(plan)
            try:
                req = srv.submit(prompt, max_new_tokens=4)
                with pytest.raises(RuntimeError) as ei:
                    req.get(timeout=600)
                assert "retriable" in str(ei.value)
            finally:
                rel.set_plan(None)
            assert ("llm.chunk", "raise") in plan.fired
            retry = srv.submit(prompt, max_new_tokens=4)
            assert list(map(int, retry.get(timeout=600))) == want
        finally:
            if not was:
                rel.disable()
            srv.stop()
        assert srv._budget_avail == 23         # idle ledger balanced


class TestCompileGrid:
    def test_mixed_replay_compiles_zero_new_programs(self, model):
        """The unified step's compile grid is O(suffix-buckets): chunk
        sizes come from the same pow2 ladder as the ragged prefill, and
        offsets/tables/targets are runtime data — so a mixed-prefix
        replay (same chunk bucket, different prefix lengths and radix
        offsets) adds ZERO new programs once the buckets are warm
        (the PR 8 compile-recorder pattern)."""
        from bigdl_tpu import observability as obs
        from bigdl_tpu.llm import serving as sv
        rs = np.random.RandomState(42)
        chains = [rs.randint(0, 250, PAGE * (1 + j)).astype(np.int32)
                  for j in range(3)]

        def tails(seed):
            r2 = np.random.RandomState(seed)
            return [np.concatenate(
                [c, r2.randint(0, 250, 9 + r2.randint(0, 8))
                 .astype(np.int32)]) for c in chains]

        def keys(tag):
            return {k for k in sv._PAGED_STEP_CACHE if tag in k}

        def compiles(fn_name):
            return sum(s["compiles"] for s in obs.compile_stats()
                       if s["fn"] == fn_name)

        was = obs.enabled()
        obs.enable()
        mixed_before = keys("mixed")
        srv = LLMServer(model, max_batch=2, max_seq_len=96,
                        page_size=PAGE, num_pages=64, kvcache=True,
                        mixed=True, chunk_tokens=CHUNK,
                        pipeline_depth=2).start()
        try:
            # a long-running decode row keeps passes FUSED (the mixed
            # program, not just the solo ragged-chunk route)
            stream = srv.submit(rs.randint(0, 250, 6).astype(np.int32),
                                max_new_tokens=80)
            for p in list(chains) + tails(0):
                srv.submit(p, max_new_tokens=2).get(timeout=600)
            assert srv.mixed_passes > 0
            warm_keys = keys("mixed")
            warm_ragged = keys("prefill_ragged")
            warm_compiles = compiles("llm/step_mixed")
            # mixed-prefix replay: every chain length again, new tails,
            # shifting radix offsets — zero new programs allowed
            for seed in (1, 2, 3):
                for p in tails(seed):
                    srv.submit(p, max_new_tokens=2).get(timeout=600)
            assert keys("mixed") == warm_keys
            assert keys("prefill_ragged") == warm_ragged
            assert compiles("llm/step_mixed") == warm_compiles
            # the whole mixed grid is the chunk-bucket ladder: every
            # chunk here is <= CHUNK tokens -> ONE pow2 bucket
            assert len(warm_keys - mixed_before) <= 1
            stream.get(timeout=600)
        finally:
            srv.stop()
            if not was:
                obs.disable()


class TestGateAbsence:
    def test_disabled_mode_structural_absence(self, model):
        """``bigdl.llm.mixed.enabled`` defaults off and
        ``bigdl.llm.prefill.chunk_tokens`` is only read behind it: the
        default engine must be structurally split — no chunk state, no
        chunk dispatches, and none of the
        ``bigdl_llm_pass_rows_total`` / ``bigdl_llm_prefill_chunks_total``
        / ``bigdl_llm_pass_mix`` series even with observability on."""
        from bigdl_tpu import observability as obs
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, 250, 26).astype(np.int32),
                   rs.randint(0, 250, 7).astype(np.int32)]
        series_names = ("bigdl_llm_pass_rows_total",
                        "bigdl_llm_prefill_chunks_total",
                        "bigdl_llm_pass_mix")

        def samples(text, name):
            return sorted(l for l in text.splitlines()
                          if l.startswith(name + "{")
                          or l.startswith(name + " "))

        was = obs.enabled()
        obs.enable()
        try:
            before = obs.render()   # the registry is process-global:
            # other tests may have minted the series — the absence
            # contract here is a ZERO DELTA from this server
            srv = LLMServer(model, max_batch=2, max_seq_len=64,
                            page_size=PAGE, kvcache=True).start()
            try:
                assert srv._mixed_active is False
                assert srv._chunk_state is None
                for p in prompts:
                    srv.submit(p, max_new_tokens=3).get(timeout=600)
                assert srv.prefill_chunks_total == 0
                assert srv.mixed_passes == 0
            finally:
                srv.stop()
            after = obs.render()
            for series in series_names:
                assert samples(after, series) == samples(before, series)
        finally:
            if not was:
                obs.disable()
