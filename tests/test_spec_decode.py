"""Model-free self-speculative decoding (ISSUE 19): greedy bit-parity
vs the plain ``generate`` golden and the spec-off engine across pipeline
depths × prefix cache on/off × mixed dispatch on/off (including a spec
row sharing a radix prefix with a live chunked admission), the adaptive
draft-length backoff unit, zero-match degradation to plain decode, the
disabled-mode structural absence of the ``bigdl.llm.spec.enabled`` gate
and the O(k-buckets) compile-grid invariant over a replay.

The hard bar everything here leans on: acceptance is greedy EXACTNESS
(``kernels.sampling.spec_accept`` keeps only the draft prefix that
matches the verify chunk's own argmaxes), so speculative output must be
bit-identical to the non-speculative engine no matter how the proposer
behaves — a diverging token is a bug in the engine, never "speculation
noise".
"""

import numpy as np
import pytest

from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer
from bigdl_tpu.llm.spec import NGramProposer

pytestmark = pytest.mark.spec

PAGE = 8


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=256)


def _generate(model, p, n):
    return list(map(int, model.generate(
        np.asarray(p)[None], max_new_tokens=n)[0, len(p):]))


def _serve(model, prompts, lens, *, spec, max_seq_len=128, **kw):
    srv = LLMServer(model, max_batch=2, max_seq_len=max_seq_len,
                    page_size=PAGE, spec=spec,
                    **kw).start()
    try:
        got = [list(map(int, r.get(timeout=600))) for r in
               [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, lens)]]
        return got, srv
    finally:
        srv.stop()


def _workload():
    """One prompt whose greedy CONTINUATION falls into a short cycle
    (seed 42 — what must repeat for prompt-lookup to draft is the
    output, not just the prompt) plus a short non-repetitive one, so
    every pass mixes a speculating row with a plain-decode row."""
    rs = np.random.RandomState(42)
    pattern = rs.randint(0, 250, 5).astype(np.int32)
    prompts = [np.tile(pattern, 6).astype(np.int32),     # 30 toks
               rs.randint(0, 250, 7).astype(np.int32)]
    return prompts, [24, 6]


# goldens computed once; the spec-off engine's own parity vs generate
# is the PR 4/8 proven matrix, so generate() is the single reference
_GOLDEN = {}


def _golden(model):
    if not _GOLDEN:
        prompts, lens = _workload()
        _GOLDEN["want"] = [_generate(model, p, n)
                           for p, n in zip(prompts, lens)]
    return _GOLDEN["want"]


class TestEngineParity:
    """The acceptance matrix: speculative outputs bit-identical to the
    golden with speculation genuinely engaged (drafts accepted, not
    just proposed)."""

    @pytest.mark.parametrize("kvcache,depth", [
        pytest.param(True, 1), pytest.param(True, 2),
        pytest.param(True, 4), pytest.param(False, 1),
        pytest.param(False, 2), pytest.param(False, 4)])
    def test_spec_parity_vs_golden(self, model, depth, kvcache):
        prompts, lens = _workload()
        want = _golden(model)
        got, srv = _serve(model, prompts, lens, spec=True, spec_k=8,
                          kvcache=kvcache, pipeline_depth=depth)
        for j, (g, w) in enumerate(zip(got, want)):
            assert g == w, f"request {j}: spec-on vs golden diverged"
        assert srv.spec_passes > 0, "speculation never engaged"
        assert srv.spec_accepted_total > 0, \
            "no draft ever accepted — the workload is not repetitive " \
            "enough to exercise the accept path"
        # the ledgers are consistent: every pass emits its bonus token
        # plus the accepted drafts, never more than it proposed
        assert srv.spec_emitted_total == \
            srv.spec_passes + srv.spec_accepted_total
        assert srv.spec_accepted_total <= srv.spec_proposed_total

    @pytest.mark.parametrize("depth", [1, 2])
    def test_spec_with_mixed_chunked_admission(self, model, depth):
        """A spec row sharing its radix prefix with a LIVE chunked
        admission: the long prompt extends the speculating row's chain
        in the radix index while that row is mid-flight, so chunk
        passes, COW adoption and speculative verifies interleave over
        the same pages — outputs must still match the goldens."""
        prompts, lens = _workload()
        rs = np.random.RandomState(7)
        long = np.concatenate(
            [prompts[0], rs.randint(0, 250, 17).astype(np.int32)])
        want = _golden(model) + [_generate(model, long, 4)]
        srv = LLMServer(model, max_batch=2, max_seq_len=128,
                        page_size=PAGE, spec=True,
                        spec_k=8, kvcache=True, mixed=True,
                        chunk_tokens=PAGE, num_pages=64,
                        pipeline_depth=depth).start()
        try:
            stream = srv.submit(prompts[0], max_new_tokens=lens[0])
            others = [srv.submit(p, max_new_tokens=n) for p, n in
                      [(prompts[1], lens[1]), (long, 4)]]
            got = [list(map(int, r.get(timeout=600)))
                   for r in [stream] + others]
            assert got == want
            assert srv.spec_passes > 0
            assert srv.prefill_chunks_total > 0, \
                "the long admission never chunked"
        finally:
            srv.stop()

    def test_zero_match_degrades_to_plain_decode(self, model):
        """A workload the proposer cannot draft for: spec-on output is
        bit-identical to spec-off, and passes that found no match paid
        nothing (plain decode ticks, no verify dispatches beyond what
        the generated history genuinely supported)."""
        rs = np.random.RandomState(1)
        prompts = [rs.randint(0, 250, 9).astype(np.int32),
                   rs.randint(0, 250, 13).astype(np.int32)]
        lens = [8, 8]
        off, _ = _serve(model, prompts, lens, spec=False,
                        pipeline_depth=2)
        on, srv = _serve(model, prompts, lens, spec=True, spec_k=8,
                         pipeline_depth=2)
        assert on == off
        # every speculative pass that DID run still reconciles
        assert srv.spec_emitted_total == \
            srv.spec_passes + srv.spec_accepted_total


class TestAdaptiveK:
    def test_backoff_halves_and_recovery_regrows(self):
        prop = NGramProposer(k=8, min_match=2, backoff=0.5)
        assert prop.k_live == 8
        # sustained rejection: EMA sinks below the backoff floor and
        # k_live halves per observation — but never below 2, because a
        # 1-token proposal carries zero drafts (the engine consumes
        # proposal[1:]) and speculation could never observe a recovery
        for _ in range(8):
            prop.observe(proposed=prop.k_live, accepted=0)
        assert prop.k_live == 2
        assert prop.acc_ema < 0.5
        # sustained acceptance: EMA recovers past the midpoint and
        # k_live climbs one step per verify back to the ceiling
        for _ in range(16):
            prop.observe(proposed=prop.k_live, accepted=prop.k_live)
        assert prop.k_live == 8
        assert prop.accept_rate < 1.0     # lifetime rate remembers both

    def test_propose_follows_the_cycle(self):
        prop = NGramProposer(k=4, min_match=2)
        ids = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
        # suffix [1, 2] recurred at positions 4..5 -> draft what
        # followed there: [3, 4, 1, 2]
        assert prop.propose(ids) == [3, 4, 1, 2]
        assert prop.last_match >= 2
        assert prop.propose(ids, limit=2) == [3, 4]
        # a constant run: the most recent occurrence is one token from
        # the end with nothing after it — the proposer must fall back
        # to an earlier occurrence that can supply real drafts (two
        # are available in a run this short; a 1-token proposal would
        # be worthless, the engine consumes proposal[1:])
        assert prop.propose([9, 7, 7, 7, 7, 7]) == [7, 7]

    def test_propose_no_match_is_empty(self):
        prop = NGramProposer(k=4, min_match=2)
        assert prop.propose([1, 2, 3, 4, 5, 6, 7]) == []
        assert prop.propose([1, 2]) == []       # too short to match
        assert prop.propose([], limit=4) == []


class TestGateAbsence:
    def test_disabled_mode_structural_absence(self, model):
        """``bigdl.llm.spec.enabled`` defaults off: the default engine
        must carry NO speculative state — no proposer slots, no pending
        set entries, no spec step cache entries, and none of the
        ``bigdl_llm_spec_*`` series even with observability on."""
        from bigdl_tpu import observability as obs
        from bigdl_tpu.utils.conf import conf
        assert conf.get_bool("bigdl.llm.spec.enabled", True) is False, \
            "the bigdl.llm.spec.enabled gate must default off"
        prompts, lens = _workload()
        series_names = ("bigdl_llm_spec_proposed_tokens_total",
                        "bigdl_llm_spec_accepted_tokens_total",
                        "bigdl_llm_spec_passes_total")

        def samples(text, name):
            return sorted(l for l in text.splitlines()
                          if l.startswith(name + "{")
                          or l.startswith(name + " "))

        was = obs.enabled()
        obs.enable()
        try:
            before = obs.render()   # process-global registry: other
            # tests may have minted the series — the absence contract
            # is a ZERO DELTA from this server
            srv = LLMServer(model, max_batch=2, max_seq_len=64,
                            page_size=PAGE, kvcache=True).start()
            try:
                assert srv._spec_active is False
                assert srv._spec_state is None
                assert srv._spec_pending == set()
                for p in prompts:
                    srv.submit(p, max_new_tokens=3).get(timeout=600)
                assert srv.spec_passes == 0
                assert srv.spec_proposed_total == 0
            finally:
                srv.stop()
            after = obs.render()
            for series in series_names:
                assert samples(after, series) == samples(before, series)
        finally:
            if not was:
                obs.disable()

    def test_spec_is_greedy_only(self, model):
        with pytest.raises(ValueError, match="greedy-only"):
            LLMServer(model, max_batch=1, max_seq_len=64,
                      page_size=PAGE, spec=True, temperature=0.7)


class TestCompileGrid:
    def test_spec_replay_compiles_zero_new_programs(self, model):
        """The spec step's compile grid is O(k-buckets): verify chunks
        pad to the pow2 bucket of ``n_draft + 1``, and the row index,
        offset, drafts and block tables are runtime data — so replaying
        the same workload (fresh request, fresh proposer, identical
        deterministic trajectory at depth 1) adds ZERO new programs
        once the buckets are warm."""
        from bigdl_tpu import observability as obs
        from bigdl_tpu.llm import serving as sv
        prompts, lens = _workload()

        def keys(tag):
            return {k for k in sv._PAGED_STEP_CACHE if tag in k}

        def compiles(fn_name):
            return sum(s["compiles"] for s in obs.compile_stats()
                       if s["fn"] == fn_name)

        was = obs.enabled()
        obs.enable()
        spec_before = keys("spec")
        srv = LLMServer(model, max_batch=2, max_seq_len=128,
                        page_size=PAGE, spec=True,
                        spec_k=8, pipeline_depth=1).start()
        try:
            for p, n in zip(prompts, lens):
                srv.submit(p, max_new_tokens=n).get(timeout=600)
            assert srv.spec_passes > 0
            warm_keys = keys("spec")
            warm_compiles = compiles("llm/step_spec")
            passes0 = srv.spec_passes
            for p, n in zip(prompts, lens):
                srv.submit(p, max_new_tokens=n).get(timeout=600)
            assert srv.spec_passes > passes0    # it speculated again
            assert keys("spec") == warm_keys
            assert compiles("llm/step_spec") == warm_compiles
            # the whole grid is the pow2 draft-bucket ladder: with
            # k=8 that is at most {2, 4, 8, 16} wide
            assert len(warm_keys - spec_before) <= 4
        finally:
            srv.stop()
            if not was:
                obs.disable()
