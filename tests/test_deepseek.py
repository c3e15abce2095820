"""The ``deepseek_v3`` family (MLA + sigmoid-routed experts) against its
plain float32 reference (``tests/deepseek_reference.py``), at tiny
widths on the CPU: the dense forward, the paged engine's prefill and
decode, the two forms of the attention, the router, the expert product,
RoPE on interleaved pairs, the engine's one latent pool and the counters.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import deepseek_reference as ref
from bigdl_tpu.llm.kernels import moe
from bigdl_tpu.llm.kernels import paged_attention as pa
from bigdl_tpu.llm.models import deepseek as ds
from bigdl_tpu.llm.models.llama import rope
from bigdl_tpu.llm.serving import LLMServer

CFG = ds.DeepseekConfig.tiny()


@pytest.fixture(scope="module")
def params32():
    return ds.init_params(CFG, seed=3, dtype=jnp.float32)


def _model(params, cache_dtype=jnp.bfloat16):
    return ds.DeepseekForCausalLM(CFG, params, max_cache_len=128,
                                  cache_dtype=cache_dtype)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


# (1) the dense forward ----------------------------------------------------

@pytest.mark.parametrize("n", [1, 23, 40])
def test_dense_forward_matches_reference(params32, n):
    ids = _ids(n)
    logits, _ = _model(params32, jnp.float32)(jnp.asarray(ids)[None])
    want, _ = ref.deepseek_logits(CFG, params32, ids)
    # float32 on both sides, another order of the same sums
    np.testing.assert_allclose(np.asarray(logits[0]), want,
                               rtol=1e-4, atol=1e-4 * np.abs(want).max())


def test_program_and_reference_choose_the_same_experts(params32):
    ids = _ids(30, seed=4)
    cache = ds.init_cache(CFG, 1, 32, jnp.float32)
    _, _, chosen = ds.forward(params32, CFG, jnp.asarray(ids)[None], cache,
                              jnp.arange(30)[None], routes=True)
    _, want = ref.deepseek_logits(CFG, params32, ids)
    assert chosen.shape == (CFG.num_moe_layers, 30, CFG.num_experts_per_tok)
    assert ref.same_experts(want, np.asarray(chosen)).all()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_deliberate_fault_moves_the_reference(params32, fault):
    ids = _ids(24, seed=5)
    good, chosen = ref.deepseek_logits(CFG, params32, ids)
    bad, chosen_bad = ref.deepseek_logits(CFG, params32, ids, fault=fault)
    moved = np.abs(bad - good).max() / good.std()
    differ = not ref.same_experts(chosen, chosen_bad).all()
    # a bfloat16 router only rounds the scores: at 8 experts it seldom
    # flips a choice, but the weights, and so the logits, still move
    least = 1e-4 if fault == "router_bf16" else 0.05
    assert moved > least or differ, (fault, moved)


# (2) prefill, then decode, through the engine's paged path ------------------

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
@pytest.mark.parametrize("n_prompt", [5, 16, 17, 47])
def test_paged_prefill_and_decode_match_reference(params32, n_prompt,
                                                  cache_dtype):
    """Prompts that end before, on and after a page boundary; the
    decode steps then cross the next one. The logits after the prefill
    and after every decode step against the reference's full forward
    over the same ids."""
    new = 20
    srv = LLMServer(_model(params32, cache_dtype), max_batch=2,
                    max_seq_len=128, pipeline_depth=1)
    prompt = _ids(n_prompt, seed=n_prompt)
    got, toks = _served_logits(srv, prompt, new)
    assert len(toks) == new and srv.pass_errors == 0
    np.testing.assert_array_equal(got.argmax(-1), np.asarray(toks))
    ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want, _ = ref.deepseek_logits(CFG, params32, ids)
    want = want[n_prompt - 1:]
    err = np.abs(got - want).max(-1) / want.std()
    if cache_dtype == jnp.float32:
        # float32 everywhere: another order of the same sums (measured
        # 2.8e-6); a wrong position, page, scale or mask is 1e-1 to 1
        assert err.max() < 1e-4
    else:
        # a bfloat16 cache rounds each cached row by up to 2^-9 of
        # itself, which reaches the logits as 3e-3 to 1e-2 of their
        # spread at these widths (measured, 80 positions). That rounding
        # may also flip a near-tie between two experts of a token (2 of
        # 8 chosen here), which moves that one position by about one
        # spread (seen at one position of the 80): so all positions but
        # at most one must lie within 3e-2
        assert np.sort(err)[-2] < 3e-2, err


def test_generate_and_the_engine_serve_the_same_tokens(params32):
    model = _model(params32)
    prompt = _ids(19, seed=9)
    srv = LLMServer(model, max_batch=3, max_seq_len=128).start()
    try:
        served = srv.submit(prompt, max_new_tokens=12).get(timeout=300)
    finally:
        srv.stop()
    want = model.generate(prompt[None], max_new_tokens=12)[0, 19:]
    np.testing.assert_array_equal(np.asarray(served), want)


# (3) the two forms of the attention -----------------------------------------

@pytest.mark.parametrize("t", [32, 2 * ds.ATTN_QUERY_BLOCK],
                         ids=["one_block", "two_query_blocks"])
def test_absorbed_attention_is_expanded_attention(params32, t):
    lp = jax.tree_util.tree_map(lambda a: a[0], params32["layers"])
    h = jnp.asarray(np.random.RandomState(1).randn(1, t, CFG.hidden_size),
                    jnp.float32)
    positions = jnp.arange(t)[None]
    q_nope, q_rope, c, k_r = ds.mla_project(lp, h, positions, CFG)
    rows = ds.latent_row(c, k_r, CFG, jnp.float32)
    assert rows.shape == (1, t, CFG.latent_width)
    absorbed = ds.mla_attend_absorbed(
        lp, q_nope, q_rope, rows, positions, jnp.ones((1, t), bool), CFG,
        jnp.float32)
    expanded = ds.mla_attend_expanded(
        lp, q_nope[0], q_rope[0], c[0], k_r[0], jnp.int32(t), CFG,
        jnp.float32)
    np.testing.assert_allclose(np.asarray(absorbed[0]),
                               np.asarray(expanded), rtol=2e-4, atol=2e-5)


def _ragged_lengths(rows=32, top=80 * 16):
    """Ragged lengths up to the table's last token, empty rows at both
    ends and in the middle."""
    lens = np.random.RandomState(5).randint(1, top + 1, rows)
    lens[[0, 9, 10, rows - 1]] = 0
    lens[3] = top
    return lens.tolist()


@pytest.mark.parametrize("maxp,lens", [
    (12, [0, 37, 150]), (80, [0, 37, 1150]),
    # a length on a block's edge, and one token past it
    (80, [512, 1024, 513]),
    # an odd and an even count of live blocks: a row ends in either slot,
    # and the next row starts in the other
    (80, [1, 600, 700, 1100, 300, 1280]),
    # nothing cached between two rows that have, and nowhere
    (80, [700, 0, 0, 900]), (80, [0, 0, 0]),
    (80, _ragged_lengths())],
    ids=["one_block", "three_blocks", "block_edges", "both_slots",
         "empty_between", "all_empty", "ragged_32_rows"])
def test_latent_kernel_matches_its_twin(maxp, lens):
    """Contexts inside one block of ``LATENT_BLOCK_TOKENS`` cached
    tokens (a table shorter than a block), and over three (the running
    maximum and sum carried from block to block); the walk's edges: a
    row hands the next its first block and its slot, a row with nothing
    cached starts and awaits no copy."""
    rs = np.random.RandomState(0)
    b, h, w, dv, page, pages = len(lens), 5, 256, 128, 16, 100
    assert max(lens) <= maxp * page
    assert (maxp * page < pa.LATENT_BLOCK_TOKENS) == (maxp == 12)
    q = jnp.asarray(rs.randn(b, h, w), jnp.float32)
    pool = jnp.asarray(rs.randn(pages, 1, page, w), jnp.bfloat16)
    bt = jnp.asarray(rs.randint(1, pages, (b, maxp)), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    want = pa.latent_attention_reference_stats(q, pool, bt, lens, dv=dv,
                                               scale=0.07)
    # the TPU interpreter: a copy lands when it is awaited and a buffer
    # nobody wrote reads NaN, so a block scored before its wait, or a
    # slot scored that no copy filled, cannot agree with the twin
    got = pa.latent_attention_decode_stats(
        q, pool, bt, lens, page_size=page, dv=dv, scale=0.07,
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait",
                                        uninitialized_memory="nan"))
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=1e-4, atol=1e-4)
    assert got[0].shape == (b, h, dv)
    # a row with nothing cached is the identity of the flash combine
    empty = np.asarray(lens) == 0
    assert not np.asarray(got[0])[empty].any()
    assert (np.asarray(got[1])[empty] < -9e29).all()
    assert not np.asarray(got[2])[empty].any()


# (4) the router ---------------------------------------------------------------

def test_router_bias_chooses_and_scores_weigh():
    cfg = CFG
    e, hid = cfg.n_routed_experts, cfg.hidden_size
    rs = np.random.RandomState(2)
    w = jnp.asarray(rs.randn(e, hid) / np.sqrt(hid), jnp.float32)
    h = jnp.asarray(rs.randn(6, hid), jnp.float32)
    s = 1 / (1 + np.exp(-(np.asarray(h) @ np.asarray(w).T)))
    plain, _ = ds.route({"w": w, "bias": jnp.zeros(e)}, h, cfg)
    # lift the expert every token ranks last above all the others
    loser = int(np.argmin(s.sum(0)))
    bias = np.zeros(e, np.float32)
    bias[loser] = 2.0
    idx, wts = ds.route({"w": w, "bias": jnp.asarray(bias)}, h, cfg)
    idx, wts = np.asarray(idx), np.asarray(wts)
    assert (idx == loser).any(-1).all()
    assert not (np.asarray(plain) == loser).any(-1).all()
    # the weights are the chosen s WITHOUT the bias, over their sum,
    # times the scaling factor
    chosen_s = np.take_along_axis(s, idx, -1)
    want = chosen_s / (chosen_s.sum(-1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling_factor
    np.testing.assert_allclose(wts, want, rtol=1e-5)
    np.testing.assert_allclose(wts.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-5)


# (5) the expert product ---------------------------------------------------------

@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("interpret", [None, True])
@pytest.mark.parametrize("t,dead", [(7, (2,)), (33, ()), (600, (0, 599))])
def test_grouped_ffn_computes_every_assignment(t, dead, interpret,
                                               activation):
    """The kernel (interpret mode) and its XLA twin, a gated expert
    (``swiglu`` over (H, 2I)) and one that is not (``relu2`` over (H,
    I))."""
    rs = np.random.RandomState(t)
    k, g, hid, width, layers = 3, 6, 32, 16, 2
    x = jnp.asarray(rs.randn(t, hid), jnp.float32)
    groups = jnp.asarray(np.stack(
        [rs.permutation(g)[:k] for _ in range(t)]), jnp.int32)
    w = jnp.asarray(rs.rand(t, k), jnp.float32)
    live = np.ones(t, bool)
    live[list(dead)] = False
    gated = activation == "swiglu"
    wgu = jnp.asarray(rs.randn(layers * g, hid, (1 + gated) * width) * 0.2,
                      jnp.float32)
    wd = jnp.asarray(rs.randn(layers * g, width, hid) * 0.2, jnp.float32)
    y, sizes = moe.grouped_ffn(x, groups, w, jnp.asarray(live), wgu, wd, 1,
                               g, interpret=interpret,
                               activation=activation)
    gu = np.einsum("th,tkhf->tkf", x, np.asarray(wgu)[np.asarray(groups) + g])
    act = gu[..., :width] / (1 + np.exp(-gu[..., :width])) \
        * gu[..., width:] if gated else np.maximum(gu, 0) ** 2
    each = np.einsum("tkf,tkfh->tkh", act,
                     np.asarray(wd)[np.asarray(groups) + g])
    want = (np.asarray(w)[..., None] * each).sum(1) * live[:, None]
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert int(sizes.sum()) == live.sum() * k        # none dropped
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(
            np.asarray(groups)[live].ravel(), minlength=g))


def test_every_token_to_the_same_experts_drops_nothing(params32):
    """The most uneven routing there is: a capacity would drop nearly
    every token here."""
    k = CFG.num_experts_per_tok
    bias = np.zeros((CFG.num_moe_layers, CFG.n_routed_experts), np.float32)
    bias[:, [3, 5][:k]] = 10.0
    params = {**params32, "layers": {**params32["layers"], "router": {
        **params32["layers"]["router"], "bias": jnp.asarray(bias)}}}
    ids = _ids(29, seed=6)
    logits, _ = _model(params, jnp.float32)(jnp.asarray(ids)[None])
    want, chosen = ref.deepseek_logits(CFG, params, ids)
    assert set(np.unique(chosen)) == {3, 5}
    np.testing.assert_allclose(np.asarray(logits[0]), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    experts = ds._flat_experts(params)
    h = jnp.asarray(np.random.RandomState(7).randn(29, CFG.hidden_size),
                    jnp.float32)
    _, stats, _ = ds.expert_layer(lp, experts, 0, h, jnp.ones(29, bool), CFG)
    assert list(np.asarray(stats)) == [29 * k, k, 29]


# (6) RoPE on interleaved pairs ---------------------------------------------------

def test_interleaved_rope_is_deinterleave_then_rotate_half():
    rs = np.random.RandomState(8)
    x = jnp.asarray(rs.randn(2, 9, 3, 16), jnp.float32)
    positions = jnp.asarray(rs.randint(0, 500, (2, 9)), jnp.int32)
    pairwise = rope(x, positions, 1e6, mode="glm")      # rotates (2i, 2i+1)
    want = jnp.concatenate([pairwise[..., 0::2], pairwise[..., 1::2]], -1)
    got = ds.rope_interleaved(x, positions, 1e6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and q.k is what the pairwise rotation gives
    y = jnp.asarray(rs.randn(2, 9, 3, 16), jnp.float32)
    np.testing.assert_allclose(
        np.asarray((got * ds.rope_interleaved(y, positions, 1e6)).sum(-1)),
        np.asarray((pairwise * rope(y, positions, 1e6, mode="glm")).sum(-1)),
        rtol=1e-4, atol=1e-4)


# (7) the engine's pool, and what refuses the family ---------------------------

def test_engine_allocates_one_latent_pool(params32):
    srv = LLMServer(_model(params32), max_batch=3, max_seq_len=64,
                    page_size=16)
    pages = 1 + 3 * 4
    assert srv._v_pages is None
    assert srv._k_pages.shape == (CFG.num_hidden_layers, pages, 1, 16,
                                  CFG.latent_width)
    assert CFG.latent_dim == 40 and CFG.latent_width == 128
    assert srv._k_pages.nbytes == CFG.num_hidden_layers * pages * 16 * 128 * 2


@pytest.mark.parametrize("kwargs,named", [
    ({"kvcache": True}, "prefix cache"),
    ({"kvcache": True, "kvtier": True}, "host tier and KV handoff"),
    ({"mixed": True}, "mixed dispatch"),
    ({"spec": True}, "speculation"),
    ({"priority": True}, "priority preemption"),
])
def test_features_that_assume_two_pools_refuse_the_family(params32, kwargs,
                                                          named):
    with pytest.raises(NotImplementedError, match=named):
        LLMServer(_model(params32), max_batch=2, max_seq_len=64, **kwargs)


@pytest.mark.parametrize("run", [False, True], ids=["decode", "prefill"])
def test_latent_pool_goes_through_the_shared_writers(run):
    """``kvcache/write.py`` reads every size from its operands: a pool
    of one 640-wide row a token is written like a K pool of 8 heads."""
    from bigdl_tpu.llm.kvcache.write import write_kv, write_kv_run
    L, P, page, w = 2, 12, 16, 640
    rs = np.random.RandomState(11)
    pool = jnp.asarray(rs.randn(L, P, 1, page, w), jnp.bfloat16)
    if run:
        t, off = 40, 19
        pos = off + np.arange(t)
        phys = np.where(pos < off + t - 3, 1 + pos // page, 0)
        slots, writer = pos % page, write_kv_run
    else:
        t = 5
        phys, slots, writer = rs.permutation(np.arange(1, P))[:t], \
            rs.randint(0, page, t), write_kv
    new = jnp.asarray(rs.randn(L, t, 1, w), jnp.float32)
    phys, slots = phys.astype(np.int32), slots.astype(np.int32)
    want = pool.at[:, phys, :, slots].set(
        new.transpose(1, 0, 2, 3).astype(pool.dtype))
    got = jax.jit(writer)(pool, jnp.asarray(phys), jnp.asarray(slots), new)
    np.testing.assert_array_equal(np.asarray(got[:, 1:], np.float32),
                                  np.asarray(want[:, 1:], np.float32))


# (8) the counters -----------------------------------------------------------------

def test_step_counters(params32):
    srv = LLMServer(_model(params32), max_batch=4, max_seq_len=128).start()
    try:
        reqs = [srv.submit(_ids(n, seed=n), max_new_tokens=m)
                for n, m in ((9, 6), (20, 11), (33, 3))]
        for r in reqs:
            r.get(timeout=300)
    finally:
        srv.stop()
    c = srv.step_counters
    k, lm, e = CFG.num_experts_per_tok, CFG.num_moe_layers, \
        CFG.n_routed_experts
    assert c["moe_token_layers_total"] > 0
    assert c["moe_assignments_total"] == k * c["moe_token_layers_total"]
    assert c["moe_layer_steps_total"] % lm == 0
    steps = c["moe_layer_steps_total"] // lm
    rows = c["moe_token_layers_total"] // lm
    assert steps <= rows <= 3 * steps
    assert c["moe_experts_touched_total"] <= sum(
        min(e, 3 * k) for _ in range(steps * lm))
    assert c["moe_experts_touched_total"] >= c["moe_layer_steps_total"] * k
    assert c["moe_max_load_total"] >= c["moe_layer_steps_total"]
    assert c["moe_max_load_total"] <= c["moe_token_layers_total"]
    # every decode step attends the prompt at least
    assert c["latent_ctx_tokens_total"] >= 9 * rows


# (9) the published configuration ---------------------------------------------------

KANANA2 = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}


def test_from_hf_config_gives_the_published_widths():
    cfg = ds.DeepseekConfig.from_hf_config(KANANA2)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.qk_head_dim,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank) == (2048, 32, 192, 128, 64, 128, 512)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.n_shared_experts, cfg.first_k_dense_replace) == \
        (6144, 768, 128, 6, 2, 1)
    assert (cfg.num_hidden_layers, cfg.num_moe_layers, cfg.vocab_size,
            cfg.rope_theta, cfg.rms_norm_eps, cfg.routed_scaling_factor) \
        == (48, 47, 128256, 1e6, 1e-6, 2.448)
    assert (cfg.latent_dim, cfg.latent_width) == (576, 640)
    assert abs(cfg.attn_scale - 192 ** -0.5) < 1e-12
    shapes = ds.linear_shapes(cfg)
    assert shapes == {"q_proj": (6144, 2048), "kv_a_proj": (576, 2048),
                      "kv_b_proj": (8192, 512), "o_proj": (2048, 4096)}
    # the benchmark's configuration file is this config at 8 layers
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "kanana2_30b_a3b_bf16.json")
    with open(path) as f:
        held = json.load(f)
    assert {k: held[k] for k in KANANA2 if k != "num_hidden_layers"} == \
        {k: v for k, v in KANANA2.items() if k != "num_hidden_layers"}
    assert held["num_hidden_layers"] == 8 and held["reduced"] == [
        "num_hidden_layers"]


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 40})])
def test_from_hf_config_refuses_what_it_does_not_implement(key, value):
    hf = {**KANANA2, key: value}
    if key == "q_lora_rank":
        # the query's down-projection is implemented (GLM-5's); an
        # indexer without it, which reads the projected query, is not
        hf = {**KANANA2, "index_topk": 2048}
    with pytest.raises(NotImplementedError, match=key.split("_")[0]):
        ds.DeepseekConfig.from_hf_config(hf)
