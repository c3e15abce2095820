"""Ragged in-place prefill through the ENGINE (ISSUE 8), the engine's
one prefill on every platform (ISSUE 29): greedy bit-parity against the
plain ``generate`` golden — pipeline depths 1/2/4, prefix cache on/off,
the COW tail fork, tier re-prefills — plus the compile-grid regression
the ragged path exists to buy: partial-prefill signatures are
O(suffix-buckets), independent of how many prefix-page buckets the
traffic mixes; a default engine of every family compiles the two
programs the chip runs and no other; the engine composes the
speculative step for a family that defines only its two programs.
(Kernel-level interpret parity lives in tests/test_paged_attention.py.)
"""

import numpy as np
import pytest

from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer

pytestmark = pytest.mark.kernels

PAGE = 8


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=128)


def _generate(model, p, n):
    return model.generate(np.asarray(p)[None], max_new_tokens=n)[0, len(p):]


def _serve(model, prompts, lens, *, replay=1, max_seq_len=64, **kw):
    """Run the workload ``replay`` times through one server; return the
    LAST pass's outputs and the (stopped) server for its counters."""
    srv = LLMServer(model, max_batch=2, max_seq_len=max_seq_len,
                    page_size=PAGE, **kw).start()
    try:
        for _ in range(replay):
            got = [r.get(timeout=600) for r in
                   [srv.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, lens)]]
        return got, srv
    finally:
        srv.stop()


def _workload():
    rs = np.random.RandomState(8)
    shared = rs.randint(0, 250, 20).astype(np.int32)      # 2.5 pages:
    prompts = [np.concatenate(                            # COW tail fork
        [shared, rs.randint(0, 250, 1 + j).astype(np.int32)])
        for j in range(4)]
    prompts.append(rs.randint(0, 250, 7).astype(np.int32))  # disjoint
    return prompts, [4, 3, 5, 2, 4]


@pytest.fixture(scope="module")
def golden(model):
    """``generate()`` over the workload, computed once for the matrix."""
    return [_generate(model, p, n) for p, n in zip(*_workload())]


def _family(name):
    """``(model, has_v_pool)`` of a tiny model of the named family."""
    if name == "llama":
        return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                            max_cache_len=64), True
    if name == "gptneox":
        from bigdl_tpu.llm.models.gptneox import (
            GptNeoXConfig as C, GptNeoXForCausalLM as M)
    elif name == "starcoder":
        from bigdl_tpu.llm.models.starcoder import (
            StarCoderConfig as C, StarCoderForCausalLM as M)
    else:
        from bigdl_tpu.llm.models.deepseek import (
            DeepseekConfig as C, DeepseekForCausalLM as M)
    return M.from_config(C.tiny(), seed=0, max_cache_len=64), \
        name != "deepseek"


class TestEngineParity:
    """The acceptance matrix: served outputs must be bit-identical to
    the plain generate golden."""

    # the full depth sweep with the cache on (every prefix-hit shape)
    # and off (every prompt the offset-0 case of the same program)
    @pytest.mark.parametrize("kvcache,depth", [
        pytest.param(True, 1), pytest.param(True, 2),
        pytest.param(True, 4), pytest.param(False, 1),
        pytest.param(False, 2), pytest.param(False, 4)])
    def test_parity_vs_golden(self, model, golden, depth, kvcache):
        prompts, lens = _workload()
        rag, srv = _serve(model, prompts, lens, replay=2,
                          kvcache=kvcache, pipeline_depth=depth)
        for j, (r, w) in enumerate(zip(rag, golden)):
            np.testing.assert_array_equal(np.asarray(r), w,
                                          err_msg=f"request {j}")
        if kvcache:
            assert srv._kv.hits > 0    # replay actually hit the prefix
            assert srv.prefix_tokens_saved > 0

    # one family in tier-1 guards the nonzero-offset layer-scan shape;
    # the second rides the slow suite (same structure, MQA/wpe variant)
    @pytest.mark.parametrize("family", [
        "gptneox", pytest.param("starcoder", marks=pytest.mark.slow)])
    def test_family_partial_offset_parity(self, family):
        """The hand-written NeoX/StarCoder ragged layer scans at a
        NONZERO runtime offset — mid-page prefix (COW tail fork),
        position-dependent math (partial rotary / learned wpe) past the
        offset: served must match the facade golden."""
        fam_model, _ = _family(family)
        rs = np.random.RandomState(5)
        shared = rs.randint(0, 250, 20).astype(np.int32)  # 2.5 pages
        prompts = [np.concatenate(
            [shared, rs.randint(0, 250, 2 + j).astype(np.int32)])
            for j in range(2)]
        lens = [3, 3]
        want = [_generate(fam_model, p, n)
                for p, n in zip(prompts, lens)]
        rag, srv = _serve(fam_model, prompts, lens, replay=2,
                          kvcache=True, max_seq_len=48)
        for j, (r, w) in enumerate(zip(rag, want)):
            np.testing.assert_array_equal(np.asarray(r), w,
                                          err_msg=f"request {j}")
        assert srv._kv.hits > 0          # offsets were really nonzero

    def test_tier_reprefill_parity(self, model):
        """ISSUE 6 composition: chains spilled to the host arena are
        re-adopted by admission and attended WHERE THEY LAND — the tier
        re-prefill rides the same ragged path and stays bit-exact."""
        from bigdl_tpu.utils.conf import conf
        rs = np.random.RandomState(23)
        groups = [rs.randint(0, 250, 16).astype(np.int32)
                  for _ in range(4)]
        prompts = [np.concatenate(
            [groups[j % 4], rs.randint(0, 250, 1 + j % 4)
             .astype(np.int32)]) for j in range(8)]
        lens = [int(rs.randint(1, 5)) for _ in prompts]
        want = [_generate(model, p, n) for p, n in zip(prompts, lens)]
        conf.set("bigdl.llm.kvtier.sync", "true")
        try:
            got, srv = _serve(model, prompts, lens, num_pages=9,
                              kvcache=True, kvtier=True, host_pages=32)
            spills, fetches = srv._tier.spills, srv._tier.fetches
        finally:
            conf.unset("bigdl.llm.kvtier.sync")
        for j, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(np.asarray(g), w,
                                          err_msg=f"request {j}")
        assert spills > 0 and fetches > 0   # the tier actually cycled


class TestCompileGrid:
    def test_partial_prefill_signatures_o_suffix_buckets(self, model):
        """The logarithmic-compile invariant (prefill.py docstring),
        post-ISSUE 8: prefix length is runtime block-table data, so a
        mixed-prefix replay adds ZERO new partial-prefill programs once
        the suffix buckets are warm. Guarded via the PR 3 compile
        recorder + the engine's step cache."""
        from bigdl_tpu import observability as obs
        from bigdl_tpu.llm import serving as sv
        rs = np.random.RandomState(42)
        # prefix chains at 1/2/3/4 pages (n_pp buckets 1, 2, 4, 4);
        # every tail is 1..4 tokens -> ONE suffix bucket (PAGE)
        chains = [rs.randint(0, 250, PAGE * (1 + j)).astype(np.int32)
                  for j in range(4)]
        def tails(seed):
            r2 = np.random.RandomState(seed)
            return [np.concatenate(
                [c, r2.randint(0, 250, 1 + r2.randint(0, 4))
                 .astype(np.int32)]) for c in chains]

        def keys(tag):
            return {k for k in sv._PAGED_STEP_CACHE if tag in k}

        def ragged_compiles():
            return sum(s["compiles"] for s in obs.compile_stats()
                       if s["fn"] == "llm/prefill_ragged")

        was = obs.enabled()
        obs.enable()
        ragged_before = keys("prefill_ragged")
        # pool roomy enough that no chain ever evicts: a miss would
        # rerun the FULL prompt, in another suffix bucket
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=PAGE, num_pages=40,
                        kvcache=True).start()
        try:
            # warmup: seed the chains (full prefill) + one partial each
            for p in list(chains) + tails(0):
                srv.submit(p, max_new_tokens=2).get(timeout=600)
            warm_keys = keys("prefill_ragged")
            warm_compiles = ragged_compiles()
            # mixed-prefix replay: every chain length again, new tails
            for seed in (1, 2, 3):
                for p in tails(seed):
                    srv.submit(p, max_new_tokens=2).get(timeout=600)
            assert keys("prefill_ragged") == warm_keys
            assert ragged_compiles() == warm_compiles
            # the whole grid is the suffix buckets: this workload's
            # are {8, 16, 32} (seeding fulls + the partial bucket), so
            # at most 3 NEW programs exist no matter how many prefix-
            # page buckets the chains span (the step cache is process-
            # global, hence the delta + subset form)
            assert len(warm_keys - ragged_before) <= 3
            assert {k[-1] for k in warm_keys - ragged_before} <= \
                {8, 16, 32}
        finally:
            srv.stop()
            if not was:
                obs.disable()


@pytest.mark.parametrize("family", ["llama", "gptneox", "starcoder",
                                    "deepseek"])
def test_default_engine_compiles_what_the_chip_runs(family):
    """A default-constructed engine on the CPU (no prefill argument, no
    conf key: there is none) serves two prompts that share 2.5 pages —
    a full prefill, then a prefix hit with a COW tail fork where the
    family has a V pool to cache prefixes in — bit-identically to
    ``generate()``, through the two programs the benchmark's cells run
    on the chip and no other kind."""
    from bigdl_tpu.llm.serving import compiled_steps
    fam_model, has_v = _family(family)
    rs = np.random.RandomState(11)
    shared = rs.randint(0, 250, 40).astype(np.int32)   # 2.5 pages of 16
    prompts = [np.concatenate(
        [shared, rs.randint(0, 250, 3 + j).astype(np.int32)])
        for j in range(2)]
    want = [_generate(fam_model, p, 4) for p in prompts]
    before = {id(fn) for _, _, fn in compiled_steps()}
    srv = LLMServer(fam_model, max_batch=2, max_seq_len=64,
                    **({"kvcache": True} if has_v else {})).start()
    try:
        got = [srv.submit(p, max_new_tokens=4).get(timeout=600)
               for p in prompts]
    finally:
        srv.stop()
    for j, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), w,
                                      err_msg=f"request {j}")
    if has_v:
        assert srv.prefix_tokens_saved == 40    # the second one hit
    # the step cache is process-global: the kinds THIS engine added
    # are these two or, in a process that had them already, none
    steps = compiled_steps()
    assert {kind for kind, _, fn in steps if id(fn) not in before} \
        <= {"prefill_ragged", "decode"}
    assert {"prefill_ragged", "decode"} <= {kind for kind, _, _ in steps}


@pytest.mark.parametrize("family", ["gptneox", "starcoder"])
def test_engine_composes_spec_step_for_a_facade_family(family):
    """``spec=True`` on a family whose module defines its two programs
    and nothing else: the engine builds the verify step itself
    (``make_spec_step``) and the served tokens stay bit-identical to
    greedy ``generate()`` with speculation really engaged."""
    fam_model, _ = _family(family)
    rs = np.random.RandomState(42)
    prompt = np.tile(rs.randint(0, 250, 5), 6).astype(np.int32)
    want = _generate(fam_model, prompt, 24)
    srv = LLMServer(fam_model, max_batch=2, max_seq_len=64,
                    page_size=PAGE, spec=True, spec_k=8).start()
    try:
        got = srv.submit(prompt, max_new_tokens=24).get(timeout=600)
    finally:
        srv.stop()
    np.testing.assert_array_equal(np.asarray(got), want)
    assert srv.spec_passes > 0, "speculation never engaged"
    assert srv.spec_emitted_total == \
        srv.spec_passes + srv.spec_accepted_total
