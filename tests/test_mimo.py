"""The ``mimo_v2`` family (window and full layers in two page classes, K
wider than V, a sink, an expert-parallel share) against its plain
float32 reference (``tests/mimo_reference.py``), at tiny widths on the
CPU: the dense forward, prefill then decode through the engine's
two-class cache, each kernel against its XLA twin, the share, the ring
allocator, the refusals.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import mimo_reference as ref
from bigdl_tpu.llm.kernels import hybrid_attention as ha
from bigdl_tpu.llm.kernels import moe
from bigdl_tpu.llm.kernels.paged_attention import merge_attention_partial
from bigdl_tpu.llm.kvcache.classes import (PageClass, RingLedger,
                                           page_classes_of)
from bigdl_tpu.llm.models import deepseek as ds
from bigdl_tpu.llm.models import llama, mimo
from bigdl_tpu.llm.serving import LLMServer

CFG = mimo.MimoConfig.tiny()
PAGE = 8
RING = ha.ring_pages(CFG.sliding_window, PAGE)      # 16 pages, 128 tokens


@pytest.fixture(autouse=True, scope="module")
def _leave_no_executables():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params32():
    return mimo.init_params(CFG, seed=3, dtype=jnp.float32)


def _model(params, cache_dtype=jnp.bfloat16, cfg=CFG):
    return mimo.MimoForCausalLM(cfg, params, max_cache_len=256,
                                cache_dtype=cache_dtype)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


# (1) the dense forward ----------------------------------------------------

@pytest.mark.parametrize("n", [1, 23, 70])
def test_dense_forward_matches_reference(params32, n):
    """70 positions are more than four windows of 16."""
    ids = _ids(n)
    logits, _ = _model(params32, jnp.float32)(jnp.asarray(ids)[None])
    want, _ = ref.mimo_logits(CFG, params32, ids)
    np.testing.assert_allclose(np.asarray(logits[0]), want,
                               rtol=1e-4, atol=1e-4 * np.abs(want).max())


def test_program_and_reference_choose_the_same_experts(params32):
    ids = _ids(30, seed=4)
    cache = mimo.init_cache(CFG, 1, 32, jnp.float32)
    _, _, chosen = mimo.forward(params32, CFG, jnp.asarray(ids)[None],
                                cache, jnp.arange(30)[None], routes=True)
    _, want = ref.mimo_logits(CFG, params32, ids)
    assert len(chosen) == CFG.num_moe_layers == 3
    assert ref.same_experts(want, [np.asarray(c) for c in chosen]).all()


def test_from_hf_config_reads_the_share_and_refuses_what_it_lacks():
    hf = {"model_type": "mimo_v2", "hidden_size": 64, "head_dim": 24,
          "v_head_dim": 16, "num_attention_heads": 8,
          "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
          "num_hidden_layers": 2, "hybrid_layer_pattern": [0, 1],
          "moe_layer_freq": [0, 1], "n_routed_experts": 4,
          "published": {"n_routed_experts": 16}, "first_expert": 8,
          "layernorm_epsilon": 1e-5, "rope_theta": 10000000,
          "swa_rope_theta": 10000, "routed_scaling_factor": None,
          "rope_scaling": {"rope_type": "default", "type": "default"},
          "n_shared_experts": None, "sliding_window": 16,
          "sliding_window_size": 16}
    cfg = mimo.MimoConfig.from_hf_config(hf)
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.experts_held) \
        == (16, 8, 4)
    assert cfg.routed_scaling_factor == 1.0 and cfg.rotary_dim == 8
    with pytest.raises(NotImplementedError, match="n_shared_experts"):
        mimo.MimoConfig.from_hf_config({**hf, "n_shared_experts": 1})
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        mimo.MimoConfig.from_hf_config(
            {**hf, "rope_scaling": {"rope_type": "yarn"}})


# (2) prefill, then decode, through the engine's two-class cache -------------

def _served_logits(srv, prompt, new):
    """Drive the engine by hand at depth 1: the logits row the engine
    holds after the prefill and after every decode step, and the tokens
    it served."""
    req = srv.submit(prompt, max_new_tokens=new)
    srv._admit()
    slot = srv._slots.index(req)
    rows = [np.asarray(srv._last[slot])]
    while not req.done.is_set():
        srv._step_paged()
        rows.append(np.asarray(srv._last[slot]))
    return np.stack(rows[:new]), list(req.tokens)


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32cache", "bf16cache"])
@pytest.mark.parametrize("n_prompt,new", [(5, 20), (50, 20), (100, 60)])
def test_paged_prefill_and_decode_match_reference(params32, n_prompt, new,
                                                  cache_dtype):
    """Prompts shorter than a window, longer than three windows (50 >
    3 x 16: two prefill chunks of 32) and, with 100 + 60 positions over
    a ring of 128, decode past the ring's wrap. The logits after the
    prefill and after every decode step against the reference's full
    forward over the same ids."""
    srv = LLMServer(_model(params32, cache_dtype), max_batch=2,
                    max_seq_len=256, page_size=PAGE, pipeline_depth=1)
    prompt = _ids(n_prompt, seed=n_prompt)
    got, toks = _served_logits(srv, prompt, new)
    assert len(toks) == new and srv.pass_errors == 0
    np.testing.assert_array_equal(got.argmax(-1), np.asarray(toks))
    ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want, _ = ref.mimo_logits(CFG, params32, ids)
    want = want[n_prompt - 1:]
    err = np.abs(got - want).max(-1) / want.std()
    if cache_dtype == jnp.float32:
        # float32 everywhere: another order of the same sums; a wrong
        # position, page, ring column, scale, sink or mask is 1e-1 to 1
        assert err.max() < 1e-4, err
    else:
        # a bfloat16 cache rounds each cached row by up to 2^-9 of
        # itself; that may also flip a near-tie between two experts of
        # a token, which moves that position by about one spread: all
        # positions but at most two must lie within 3e-2
        assert np.sort(err)[-3] < 3e-2, err
    c = srv.step_counters
    assert c["moe_assignments_total"] + c["moe_assignments_elsewhere_total"] \
        == CFG.num_experts_per_tok * c["moe_token_layers_total"]
    # a step a served token: the step that samples token k caches
    # the one before it
    lens = n_prompt + np.arange(new)
    assert c["full_ctx_tokens_total"] == lens.sum()
    assert c["window_ctx_tokens_total"] == \
        np.minimum(lens, CFG.sliding_window).sum()


def test_generate_and_the_engine_serve_the_same_tokens(params32):
    model = _model(params32)
    prompt = _ids(70, seed=9)
    srv = LLMServer(model, max_batch=3, max_seq_len=256,
                    page_size=PAGE).start()
    try:
        others = [srv.submit(_ids(n, seed=n), max_new_tokens=30)
                  for n in (5, 33)]
        served = srv.submit(prompt, max_new_tokens=40).get(timeout=300)
        for o in others:
            o.get(timeout=300)
    finally:
        srv.stop()
    assert srv.pass_errors == 0
    want = model.generate(prompt[None], max_new_tokens=40)[0, 70:]
    np.testing.assert_array_equal(np.asarray(served), want)
    assert srv.pages_in_use_by_class == {"full": 0, "window": 0}


# (3) each kernel against its XLA twin, interpret mode ------------------------

def _pool(rs, pages, hkv, dk=128, dv=128, used_k=24, used_v=16,
          dtype=jnp.float32):
    """A pool of ``[key | value]`` rows that are zero beyond the model's
    own widths, as the engine's are."""
    kv = np.zeros((pages, hkv, PAGE, dk + dv), np.float32)
    kv[..., :used_k] = rs.randn(pages, hkv, PAGE, used_k)
    kv[..., dk:dk + used_v] = rs.randn(pages, hkv, PAGE, used_v)
    return jnp.asarray(kv, dtype)


# what a walk inside the kernel can get wrong and a grid could not: case
# -> (cached lengths, table columns of the full class); a block of the
# walk is 64 pages of 8 = 512 tokens
_WALKS = {
    "mixed": ([0, 7, 130, 131 + 128, 300], 48),
    "first_live_row_is_not_0": ([0, 0, 600, 30, 1100], 144),
    "dead_rows_between_and_at_the_end": ([513, 0, 0, 1025, 0, 9, 0, 0],
                                         144),
    "every_row_dead": ([0, 0, 0, 0], 144),
    "one_row": ([700], 144),                    # the check's probe
    "whole_blocks_and_a_token_more": ([512, 513, 1024, 1025], 144),
    # 1, 2, 3, 2, 1, 3 blocks: the starting slot flips from row to row
    "odd_then_even_block_counts": ([512, 1000, 1100, 600, 40, 1500], 208),
    "table_shorter_than_a_block": ([39, 0, 8, 40], 5),
}


@pytest.mark.parametrize(
    "hkv,window,case",
    [(hkv, w, "mixed") for hkv in (2, 4) for w in (None, 16, 40)]
    + [(4 if w else 2, w, case) for case in list(_WALKS)[1:]
       for w in (None, 16)],
    ids=lambda v: {2: "4kv_like", 4: "8kv_like"}.get(v, str(v)))
def test_decode_kernel_matches_its_twin(hkv, window, case):
    """Rows of no cached token, one short of a page, several pages,
    several blocks, and (ring) lengths past one and several wraps."""
    rs = np.random.RandomState(7)
    lens, cols = _WALKS[case]
    b, hq = len(lens), 8
    if window:
        cols = ha.ring_pages(window, PAGE)
    kv = _pool(rs, 1 + b * cols, hkv)
    bt = (1 + np.arange(b * cols).reshape(b, cols)).astype(np.int32)
    lens = np.asarray(lens, np.int32)
    q = np.zeros((b, hq, 128), np.float32)
    q[..., :24] = rs.randn(b, hq, 24)
    args = (jnp.asarray(q), kv, jnp.asarray(bt), jnp.asarray(lens))
    want = ha.attention_decode_reference_stats(
        *args, scale=24 ** -0.5, window=window)
    # the TPU interpreter: a copy lands when it is awaited and a buffer
    # nobody wrote reads NaN, so a block scored before its wait, or a
    # slot scored that no copy filled, cannot agree with the twin
    got = ha.attention_decode_stats(
        *args, page_size=PAGE, scale=24 ** -0.5, window=window,
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait",
                                        uninitialized_memory="nan"))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)
    dead = lens == 0                            # nothing cached: identity
    for g, identity in zip(got, (0.0, -1e30, 0.0)):
        assert (np.asarray(g)[dead] == np.float32(identity)).all()


@pytest.mark.parametrize("window,sink", [(None, False), (16, True),
                                         (40, True), (16, False)])
@pytest.mark.parametrize("off,tq,slen", [(0, 32, 32), (64, 32, 19),
                                         (8 * 19 + 3, 64, 64)])
def test_prefill_kernel_matches_its_twin(window, sink, off, tq, slen):
    rs = np.random.RandomState(11)
    hq, hkv = 8, 2 if window is None else 4
    cols = ha.ring_pages(window, PAGE) if window else 32
    kv = _pool(rs, 1 + cols, hkv)
    bt = (1 + np.arange(cols))[None].astype(np.int32)
    q = np.zeros((1, tq, hq, 128), np.float32)
    q[..., :24] = rs.randn(1, tq, hq, 24)
    ks = np.zeros((1, tq, hkv, 128), np.float32)
    ks[..., :24] = rs.randn(1, tq, hkv, 24)
    vs = np.zeros((1, tq, hkv, 128), np.float32)
    vs[..., :16] = rs.randn(1, tq, hkv, 16)
    s = jnp.asarray(rs.randn(hq), jnp.float32) if sink else None
    args = (jnp.asarray(q), jnp.asarray(ks), jnp.asarray(vs), kv,
            jnp.asarray(bt), jnp.asarray([off], jnp.int32),
            jnp.asarray([slen], jnp.int32), s)
    want = ha.prefill_attention_reference(*args, scale=24 ** -0.5,
                                          window=window)
    got = ha.prefill_attention(*args, page_size=PAGE, scale=24 ** -0.5,
                               window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0, :slen],
                               np.asarray(want)[0, :slen],
                               rtol=2e-5, atol=2e-5)


def test_sink_joins_the_denominator_of_the_merge():
    """One cached key, the current token and the sink: the three-way
    softmax by hand."""
    q = jnp.ones((1, 2, 4), jnp.float32)
    k_new = jnp.asarray([[[1.0, 0, 0, 0]]])
    v_new = jnp.asarray([[[2.0, 0]]])
    sink = jnp.asarray([0.5, -1.0])
    s_old, v_old = 0.25, jnp.asarray([1.0, 3.0])
    acc = jnp.broadcast_to(v_old, (1, 2, 2))       # weight exp(0) = 1
    m = jnp.full((1, 2), s_old)
    out = merge_attention_partial(acc, m, jnp.ones((1, 2)), q, k_new,
                                  v_new, scale=1.0, sink=sink)
    for h in range(2):
        e = np.exp([s_old, 1.0, float(sink[h])])
        want = (e[0] * np.asarray(v_old) + e[1] * np.asarray([2.0, 0])) \
            / e.sum()
        np.testing.assert_allclose(np.asarray(out[0, h]), want, rtol=1e-6)


def test_ring_positions_name_the_newest_page_of_each_column():
    page, ring = 8, 16
    for cached in (0, 1, 8, 127, 128, 129, 1000):
        cols = np.repeat(np.arange(ring), page)
        slots = np.tile(np.arange(page), ring)
        pos = np.asarray(ha.ring_positions(
            jnp.asarray(cols), jnp.asarray(slots), jnp.int32(cached),
            page, ring))
        held = pos[(pos >= 0) & (pos < cached)]
        lo = max(0, (max(cached - 1, 0) // page - ring + 1) * page)
        np.testing.assert_array_equal(np.sort(held),
                                      np.arange(lo, cached))
        ok = (pos >= 0) & (pos < cached)
        assert ((pos[ok] // page) % ring == cols[ok]).all()
        assert (pos[ok] % page == slots[ok]).all()


# (4) the share --------------------------------------------------------------

def test_the_shares_add_up_to_the_whole_expert_layer():
    """The guide's share test: the partial results of the four shares
    (4 experts each of 16), router and all, add up to the uncut
    reference's expert layer."""
    whole = mimo.MimoConfig.tiny(first_expert=0, experts_held=16)
    lp = mimo.init_params(whole, seed=5, dtype=jnp.float32)["layers"][1]
    h = jnp.asarray(np.random.RandomState(2).randn(37, 64), jnp.float32)
    live = jnp.ones(37, bool)
    with jax.default_matmul_precision("highest"):
        want, idx, _ = ref.routed_sum(
            h, lp["router"], lp["experts"]["w_gate_up"],
            lp["experts"]["w_down"], first=0, top_k=4, scaling=1.0,
            norm_topk=True)
    total, computed, elsewhere = 0.0, 0, 0
    for first in (0, 4, 8, 12):
        share = mimo.MimoConfig.tiny(first_expert=first, experts_held=4)
        part = {"router": lp["router"], "experts": jax.tree_util.tree_map(
            lambda a: a[first:first + 4], lp["experts"])}
        y, stats, got_idx = mimo.expert_layer(part, h, live, share)
        np.testing.assert_array_equal(np.sort(got_idx, -1),
                                      np.sort(np.asarray(idx), -1))
        assert int(stats[0]) + int(stats[1]) == 37 * 4
        total = total + y
        computed += int(stats[0])
        # the reference given the same share says the same
        with jax.default_matmul_precision("highest"):
            ref_part, _, _ = ref.routed_sum(
                h, lp["router"], part["experts"]["w_gate_up"],
                part["experts"]["w_down"], first=first, top_k=4,
                scaling=1.0, norm_topk=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_part),
                                   rtol=1e-4, atol=1e-5)
    assert computed == 37 * 4       # every assignment computed once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_an_assignment_held_elsewhere_takes_no_row_and_no_tile():
    groups = jnp.asarray([[0, 5], [9, 6], [4, 7]], jnp.int32)
    w = jnp.ones((3, 2), jnp.float32)
    x = jnp.ones((3, 8), jnp.float32)
    wgu = jnp.ones((4, 8, 4), jnp.float32)
    wd = jnp.ones((4, 2, 8), jnp.float32)
    y, sizes = moe.grouped_ffn(x, groups, w, jnp.asarray([1, 1, 0], bool),
                               wgu, wd, 0, 16, held=(4, 4))
    # held: experts 4..7; token 0 has one (5), token 1 one (6), token 2
    # is dead
    np.testing.assert_array_equal(np.asarray(sizes), [0, 1, 1, 0])
    assert float(jnp.abs(y[2]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y[1]))
    d = moe.dispatch(jnp.where((groups >= 4) & (groups < 8), groups - 4, 0),
                     (groups >= 4) & (groups < 8)
                     & jnp.asarray([1, 1, 0], bool)[:, None], 4, 16)
    assert int(d.n_tiles) == 2


def test_kanana_grouped_ffn_with_every_expert_held_is_what_it_was():
    """``held=None`` and a (T,) ``live`` trace the program the family
    has always traced: the same jaxpr as the code before ISSUE 31's
    range, kept here in its own words."""
    def before(x, groups_of, weights, live, w_gate_up, w_down, layer,
               n_groups):
        t, h = x.shape
        tm = moe.tile_rows(t)
        d = moe.dispatch(groups_of, live, n_groups, tm)
        x_ext = jnp.concatenate([x, jnp.zeros((1, h), x.dtype)])
        x_pad = x_ext[d.row_src]
        tg = d.tile_group + layer * n_groups
        y_pad = moe.moe_expert_ffn_reference(x_pad, w_gate_up, w_down, tg,
                                             d.n_tiles, tm=tm)
        w = jnp.where(live[:, None], weights.astype(jnp.float32), 0.0)
        y = jnp.where((w != 0)[..., None], w[..., None] * y_pad[d.pos], 0.0)
        return y.sum(axis=1), d.group_sizes

    cfg = ds.DeepseekConfig.tiny()
    rs = np.random.RandomState(0)
    args = (jnp.asarray(rs.randn(9, 64), jnp.bfloat16),
            jnp.asarray(rs.randint(0, cfg.n_groups, (9, 3)), jnp.int32),
            jnp.asarray(rs.rand(9, 3), jnp.float32),
            jnp.asarray(rs.rand(9) > 0.2),
            jnp.asarray(rs.randn(2 * cfg.n_groups, 64, 64), jnp.bfloat16),
            jnp.asarray(rs.randn(2 * cfg.n_groups, 32, 64), jnp.bfloat16))
    now = lambda *a: moe.grouped_ffn(*a, 1, cfg.n_groups)
    then = lambda *a: before(*a, 1, cfg.n_groups)
    assert str(jax.make_jaxpr(now)(*args)) == \
        str(jax.make_jaxpr(then)(*args))
    for a, b in zip(now(*args), then(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deepseek_route_is_the_shared_router():
    cfg = ds.DeepseekConfig.tiny()
    rs = np.random.RandomState(1)
    router = {"w": jnp.asarray(rs.randn(8, 64), jnp.float32),
              "bias": jnp.asarray(0.05 * rs.randn(8), jnp.float32)}
    h = jnp.asarray(rs.randn(11, 64), jnp.float32)
    idx, w = ds.route(router, h, cfg)
    idx2, w2 = moe.route_sigmoid(router, h, cfg.num_experts_per_tok,
                                 cfg.norm_topk_prob,
                                 cfg.routed_scaling_factor)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
    np.testing.assert_allclose(np.asarray(w.sum(-1)),
                               cfg.routed_scaling_factor, rtol=1e-5)


# (5) the classes and the ring's ledger ---------------------------------------

def test_every_family_declares_through_one_hook():
    lc = llama.LlamaConfig.tiny()
    assert page_classes_of(llama, lc) == [PageClass(
        "kv", lc.num_hidden_layers, lc.num_key_value_heads, lc.head_dim,
        lc.head_dim)]
    (latent,) = page_classes_of(ds, ds.DeepseekConfig.tiny())
    assert latent.v_width is None and latent.kv_heads == 1
    full, window = page_classes_of(mimo, CFG)
    assert (full.layers, full.kv_heads, full.keeps) == (2, 2, None)
    assert (window.layers, window.kv_heads, window.keeps) == (2, 4, 16)
    # rows [k | v], 24 and 16 numbers held a lane block each; no V pool
    assert full.k_width == window.k_width == 256
    assert full.v_width is None and window.v_width is None


def test_window_pages_a_row_never_pass_the_ring(params32):
    """A request of 40 windows (640 positions): the window class holds
    at most the ring's pages at every step, the full class grows, and
    every page of both comes back."""
    srv = LLMServer(_model(params32), max_batch=2, max_seq_len=700,
                    page_size=PAGE, pipeline_depth=1)
    (ring,) = srv._rings
    bound = -(-(CFG.sliding_window + PAGE) // PAGE)
    bound = -(-bound // (128 // PAGE)) * (128 // PAGE)
    assert ring.ring == RING == bound
    free0 = (srv._kv.pool.free_pages(), ring.pool.free_pages())
    req = srv.submit(_ids(90, seed=1), max_new_tokens=550)
    srv._admit()
    seen = []
    while not req.done.is_set():
        srv._step_paged()
        seen.append((srv.pages_in_use_by_class["window"],
                     srv.pages_in_use_by_class["full"]))
    assert len(req.tokens) == 550 and srv.pass_errors == 0
    assert max(w for w, _ in seen) == RING
    assert max(f for _, f in seen) == -(-640 // PAGE)
    assert (srv._kv.pool.free_pages(), ring.pool.free_pages()) == free0
    assert ring.pool.budget_avail == ring.num_pages - 1
    assert not ring.bt.any()


def test_ring_ledger_grants_until_the_ring_is_full_and_releases_all():
    cls = PageClass("window", 2, 4, 256, None, keeps=16)
    ring = RingLedger(cls, PAGE, max_batch=2)
    assert ring.admit(0, 1000) and ring.admit(1, 20)
    assert ring.charge == [RING, 3]
    assert [c for c, _ in ring.grant(0, 17)] == [0, 1, 2]
    assert ring.grant(0, 24) == []
    assert len(ring.grant(0, 10 ** 6)) == RING - 3
    assert ring.grant(0, 10 ** 7) == []
    targets = ring.scatter_targets(0, np.arange(120, 140), 136)
    assert (targets[:16] == ring.bt[0, (np.arange(120, 136) // PAGE)
                                    % RING]).all()
    assert not targets[16:].any()
    assert ring.release(0) == RING and ring.release(1) == 0
    assert ring.pool.free_pages() == ring.num_pages - 1
    assert ring.pool.budget_avail == ring.num_pages - 1


@pytest.mark.parametrize("feature", ["kvcache", "kvtier", "mixed", "spec",
                                     "priority"])
def test_what_moves_pages_of_one_class_refuses_the_family(params32,
                                                          feature):
    with pytest.raises(NotImplementedError,
                       match=r"2 classes \(full, window\)"):
        LLMServer(_model(params32), max_batch=2, max_seq_len=64,
                  page_size=PAGE, **{feature: True})


def test_the_two_copies_of_the_reference_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "mimo_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmark",
                           "reference_mimo.py")) as f:
        theirs = f.read()

    def below_header(text):
        return text.split('"""', 2)[2]
    assert below_header(mine) == below_header(theirs)
