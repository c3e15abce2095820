"""GLM-5's mechanisms in the ``deepseek_v3`` family (``glm_moe_dsa``:
a query down-projection, DeepSeek Sparse Attention, a share of the
experts held) against the plain float32 reference the benchmark decides
``correct`` with (``benchmark/reference_glm5.py``), at tiny widths on
the CPU where the selection binds (top-16 of prompts of 48 to 96): the
engine's prefill and decode, the three decode parts in interpret mode
against their plain forms, the prefill's selection, the expert share,
and a Kanana-shaped configuration that builds none of it.
"""

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark import reference_glm5 as ref
from bigdl_tpu.llm.kernels import sparse_attention as sa
from bigdl_tpu.llm.kvcache.classes import PageClass, page_classes_of
from bigdl_tpu.llm.models import deepseek as ds
from bigdl_tpu.llm.serving import LLMServer

CFG = ds.DeepseekConfig.tiny_dsa()
_cases = itertools.count(1)


@pytest.fixture(autouse=True)
def _drop_executables():
    """Every kernel case compiles programs of its own, and the CPU
    client keeps memory mappings an executable: drop them every 32
    cases."""
    yield
    if next(_cases) % 32 == 0:
        jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def _leave_no_executables():
    """Prefill chunks of 32 tokens, so that the prompts here take two
    and three; the programs traced with them go with the module."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ds, "PREFILL_CHUNK", 32)
        yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params32():
    return ds.init_params(CFG, seed=3, dtype=jnp.float32)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


def _served_logits(srv, prompt, new):
    """The engine driven by hand at depth 1: its logits row after the
    prefill and after every decode step, and the tokens it served."""
    req = srv.submit(prompt, max_new_tokens=new)
    srv._admit()
    slot = srv._slots.index(req)
    rows = [np.asarray(srv._last[slot])]
    while not req.done.is_set():
        srv._step_paged()
        rows.append(np.asarray(srv._last[slot]))
    return np.stack(rows[:new]), list(req.tokens)


# (1) the engine against the reference ----------------------------------------

@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32cache", "bf16cache"])
@pytest.mark.parametrize("n_prompt", [48, 61, 96])
def test_paged_prefill_and_decode_match_reference(params32, n_prompt,
                                                  cache_dtype):
    """Prompts of two and three prefill chunks (32 tokens), every one
    longer than the 16 positions a query keeps; decode steps then cross
    a page. The logits after the prefill and after each step against
    the reference's full forward over the same ids."""
    new = 12
    model = ds.DeepseekForCausalLM(CFG, params32, max_cache_len=256,
                                   cache_dtype=cache_dtype)
    srv = LLMServer(model, max_batch=2, max_seq_len=256, pipeline_depth=1)
    prompt = _ids(n_prompt, seed=n_prompt)
    got, toks = _served_logits(srv, prompt, new)
    assert len(toks) == new and srv.pass_errors == 0
    np.testing.assert_array_equal(got.argmax(-1), np.asarray(toks))
    ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want, _ = ref.glm5_logits(CFG, params32, ids)
    err = np.abs(got - want[n_prompt - 1:]).max(-1) / want.std()
    if cache_dtype == jnp.float32:
        # float32 everywhere: the same selections and another order of
        # the same sums (measured under 1e-5)
        assert err.max() < 1e-4, err
    else:
        # bfloat16 rows and index keys: the rows' rounding (1e-3 to 1e-2
        # of a spread in the median, measured over six prompts), and a
        # key rounded across a near-tie of two scores swaps the 16th and
        # the 17th position of a query at these widths, which moves that
        # position and the ones after it by 0.1 to 0.4 (up to three of
        # twelve seen); a wrong page, position or mask moves them all
        assert np.median(err) < 2e-2, err
        assert (err < 5e-2).mean() >= 2 / 3, err


def test_dense_forward_matches_reference(params32):
    ids = _ids(70, seed=5)
    cache = ds.init_cache(CFG, 1, 70, jnp.float32)
    logits, _, chosen = ds.forward(params32, CFG, jnp.asarray(ids)[None],
                                   cache, jnp.arange(70)[None], routes=True)
    routing = []
    want, want_chosen = ref.glm5_logits(CFG, params32, ids, routing=routing)
    np.testing.assert_allclose(np.asarray(logits[0]), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    assert (np.sort(np.asarray(chosen), -1)
            == np.sort(want_chosen, -1)).mean() > 0.99


# (2) the scoring kernel ------------------------------------------------------

def _pool_inputs(lens, maxp, hi=8, d=128, page=16, seed=0):
    rs = np.random.RandomState(seed)
    b = len(lens)
    pages = 1 + b * maxp
    keys = jnp.asarray(rs.randn(pages, 1, page, d), jnp.bfloat16)
    perm = 1 + rs.permutation(b * maxp).reshape(b, maxp)
    bt = jnp.asarray(perm, jnp.int32)
    q = jnp.asarray(rs.randn(b, hi, d), jnp.bfloat16)
    w = jnp.asarray(rs.randn(b, hi), jnp.float32)
    return q, w, keys, bt, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("maxp,lens", [
    (8, [0, 5, 128, 17]),
    (40, [640, 0, 0, 33, 511, 512, 1]),
    (64, [1000, 0, 1024]),
], ids=["short", "ragged_with_dead_rows", "whole_blocks"])
def test_scoring_kernel_matches_its_plain_form(maxp, lens):
    """The walk in interpret mode, DMAs waited where they are awaited,
    unwritten memory NaN, against the gather twin over the cached
    positions; with the current token's score beside them the selection
    is the same."""
    q, w, keys, bt, ln = _pool_inputs(lens, maxp)
    got = sa.index_scores_decode(
        q, w, keys, bt, ln, page_size=16, interpret=pltpu.InterpretParams(
            dma_execution_mode="on_wait", uninitialized_memory="nan"))
    want = sa.index_scores_reference(q, w, keys, bt, ln)
    live = np.arange(maxp * 16)[None] < np.asarray(lens)[:, None]
    np.testing.assert_allclose(np.where(live, got, 0), want, rtol=1e-5,
                               atol=1e-4)
    cur = jnp.asarray(np.random.RandomState(1).randn(len(lens)) * 8,
                      jnp.float32)
    k = 64
    a = sa.dsa_select(got, cur, ln, k=k)
    b = sa.dsa_select(want, cur, ln, k=k)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x)[np.asarray(a[1])],
                                      np.asarray(y)[np.asarray(b[1])])


def test_current_token_competes():
    """A current token scored above every cached one is selected; one
    scored below the k-th is not; a row shorter than k keeps all."""
    scores = jnp.asarray(np.arange(40, dtype=np.float32)[None].repeat(3, 0))
    lens = jnp.asarray([30, 30, 5], jnp.int32)
    cur = jnp.asarray([99.0, -1.0, -1.0])
    pos, ok = (np.asarray(a) for a in sa.dsa_select(scores, cur, lens, k=8))
    assert 30 in pos[0][ok[0]] and 30 not in pos[1][ok[1]]
    assert sorted(pos[2][ok[2]]) == [0, 1, 2, 3, 4, 5]
    assert ok.sum(1).tolist() == [8, 8, 6]


# (3) the selection -----------------------------------------------------------

def _argsort_selection(scores, k):
    want = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        fin = np.where(np.isfinite(row))[0]
        want[r, fin[np.argsort(-row[fin], kind="stable")][:k]] = True
    return want


@pytest.mark.parametrize("k", [1, 16, 100])
def test_selection_against_argsort_over_ties(k):
    """Scores rounded to a tenth (many ties), causal rows of every
    length: both forms keep what a stable sort keeps, ties to the lower
    position."""
    rs = np.random.RandomState(k)
    s = np.round(rs.randn(9, 256), 1).astype(np.float32)
    s[:, 100:140] = 0.5
    ends = np.asarray([0, 1, 15, 16, 17, 99, 130, 200, 255])
    causal = np.arange(256)[None] <= ends[:, None]
    masked = np.where(causal, s, -np.inf).astype(np.float32)
    want = _argsort_selection(masked, k)
    got = np.asarray(sa.select_mask(jnp.asarray(masked), k, n_blocks=4,
                                    block=64))
    np.testing.assert_array_equal(got, want)
    pos, ok = sa.dsa_select(jnp.asarray(s), jnp.asarray(s[np.arange(9),
                                                          ends]),
                            jnp.asarray(ends, jnp.int32), k=k)
    got2 = np.zeros_like(want)
    for r in range(9):
        got2[r, np.asarray(pos[r])[np.asarray(ok[r])]] = True
    np.testing.assert_array_equal(got2, want)


# the selection kernel (interpret mode, unwritten memory NaN) against the
# sort and against a plain stable argsort; scores past each row's length
# are NaN, as the scoring walk may leave them
_NAN_PAST = pltpu.InterpretParams(uninitialized_memory="nan")


def _topk_case(case):
    """(scores (B, S) NaN past each length, current (B,), lengths, k)."""
    rs = np.random.RandomState(_TOPK_CASES.index(case))
    k, s_len = 64, 2048
    if case == "lengths_about_k":
        lens = [0, 1, k - 1, k, k + 1, 2047]
    elif case == "ragged_tiles":
        k, s_len = 300, 4096
        lens = [4095, 2500, 1023, 1024, 1025, 301, 0]
    else:
        lens = [700, 1500, 2047, 90]
    s = rs.randn(len(lens), s_len).astype(np.float32)
    cur = rs.randn(len(lens)).astype(np.float32)
    if case == "ties_tenth":
        s, cur = np.round(s, 1), np.round(cur, 1)
    elif case == "flat_run":
        s[:, 50:1400] = 0.25
        cur[:] = 0.25
    elif case == "current_above":
        cur[:] = 99.0
    elif case == "current_below_kth":
        cur[:] = -99.0
    elif case == "current_tied_lower":
        # the current token ties the cached score at position 3 (one
        # kept, near the top): ties go to the lower position
        s[:, 3] = 5.0
        cur[:] = 5.0
        s[:, 4:k] = 6.0
    elif case == "signed_zeros":
        s = np.where(rs.rand(*s.shape) < 0.5, -0.0, 0.0).astype(np.float32)
        s[:, ::7] = rs.randn(len(lens), len(range(0, s_len, 7)))
        cur[:] = -0.0
    for r, n in enumerate(lens):
        s[r, n:] = np.nan
    return s, cur, np.asarray(lens, np.int32), k


_TOPK_CASES = ["lengths_about_k", "ragged_tiles", "ties_tenth", "flat_run",
               "current_above", "current_below_kth", "current_tied_lower",
               "signed_zeros"]


def _kept_sets(what, ok):
    return [sorted(np.asarray(w)[np.asarray(o)].tolist())
            for w, o in zip(what, ok)]


@pytest.mark.parametrize("table", [False, True], ids=["positions",
                                                      "pool_rows"])
@pytest.mark.parametrize("case", _TOPK_CASES)
def test_topk_kernel_selects_the_sorts_set(case, table):
    """``dsa_topk_decode`` keeps exactly the set the stable sort keeps
    (and a plain argsort over the same row with the current token at
    ``len``), carrying each position's pool row through a permuted
    table where one is given, and marks exactly ``min(k, len + 1)``
    entries selected."""
    s, cur, lens, k = _topk_case(case)
    b, n = s.shape
    pay = (np.random.RandomState(1).permutation(b * n).reshape(b, n)
           .astype(np.int32) if table else None)
    args = (jnp.asarray(s), jnp.asarray(cur), jnp.asarray(lens),
            None if pay is None else jnp.asarray(pay))
    got = sa.dsa_topk_decode(*args, k=k, interpret=_NAN_PAST)
    want = sa.dsa_select_reference(*args, k=k)
    assert _kept_sets(*got) == _kept_sets(*want)
    np.testing.assert_array_equal(np.asarray(got[1]).sum(1),
                                  np.minimum(lens + 1, k))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    row = np.where(np.arange(n)[None] < lens[:, None], s, -np.inf)
    row[np.arange(b), lens] = cur
    plain = _argsort_selection(row.astype(np.float32), k)
    for r in range(b):
        pos = np.flatnonzero(plain[r])
        assert _kept_sets(*got)[r] == sorted(
            (pos if pay is None else pay[r, pos]).tolist())


def test_topk_kernel_keeps_position_order():
    """The kernel's kept entries come in ascending position, the sort's
    in descending score."""
    s, cur, lens, k = _topk_case("ragged_tiles")
    pos, ok = sa.dsa_topk_decode(jnp.asarray(s), jnp.asarray(cur),
                                 jnp.asarray(lens), k=k, interpret=True)
    for r in range(len(lens)):
        kept = np.asarray(pos[r])[np.asarray(ok[r])]
        assert len(kept) == min(k, lens[r] + 1)
        assert (np.diff(kept) > 0).all()


def test_decode_step_with_the_topk_kernel(params32, monkeypatch):
    """A whole decode step (three layers over random float32 pools,
    rows past and under the 16 kept, a dead row) with the kernel in
    interpret mode gives the sort's logits and writes the same pools, to
    float32 rounding."""
    rs = np.random.RandomState(5)
    page, pmax = 16, 8
    lens = np.asarray([100, 9, 0, 127, 16], np.int32)
    b = len(lens)
    pages = 1 + b * pmax
    lay = CFG.num_hidden_layers
    kv = jnp.asarray(rs.randn(lay, pages, 1, page, CFG.latent_width),
                     jnp.float32)
    ik = jnp.asarray(rs.randn(lay, pages, 1, page, CFG.index_head_dim),
                     jnp.float32)
    bt = jnp.asarray(1 + rs.permutation(b * pmax).reshape(b, pmax),
                     jnp.int32)
    toks = jnp.asarray(rs.randint(0, CFG.vocab_size, b), jnp.int32)

    def step():
        return ds.paged_decode_step(params32, CFG, kv, ik, bt,
                                    jnp.asarray(lens), toks, page=page)

    want = step()
    monkeypatch.setattr(sa, "dsa_select", functools.partial(
        sa.dsa_select, interpret=True))
    got = step()
    # the same sums in another order: float32 rounding apart
    for x, y in zip(got[:3], want[:3]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-5)


# (4) the sparse attention ----------------------------------------------------

@pytest.mark.parametrize("interpret", [None, True], ids=["twin", "kernel"])
def test_sparse_attention_is_dense_attention_under_the_mask(interpret):
    """Selected rows gathered through a table, attended by the kernel
    (or its twin) and folded with the current token where it was
    selected, against plain softmax attention over the whole row under
    the same selection."""
    rs = np.random.RandomState(7)
    b, h, w, dv, page, maxp, k = 3, 8, 256, 128, 16, 12, 64
    lens = np.asarray([150, 9, 191])
    pool = jnp.asarray(rs.randn(1 + b * maxp, 1, page, w), jnp.float32)
    bt = jnp.asarray(1 + rs.permutation(b * maxp).reshape(b, maxp),
                     jnp.int32)
    q = jnp.asarray(rs.randn(b, h, w), jnp.float32)
    cur = jnp.asarray(rs.randn(b, w), jnp.float32)
    scores = jnp.asarray(rs.randn(b, maxp * page), jnp.float32)
    current = jnp.asarray([9.0, -9.0, -9.0])
    pos, ok = sa.dsa_select(scores, current, jnp.asarray(lens), k=k)
    # the decode step's form: pool rows carried through the selection
    at, okr = sa.dsa_select(scores, current, jnp.asarray(lens),
                            sa.pool_rows(bt, jnp.asarray(lens), page=page),
                            k=k)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(okr))
    cached = okr & (at >= 0)
    rows = sa.gather_selected(pool.reshape(-1, w), at)
    acc, m, l = sa.sparse_latent_stats(q, rows, cached, dv=dv, scale=0.1,
                                       interpret=interpret)
    take = jnp.any(okr & (at < 0), axis=1)
    got = np.asarray(sa.fold_current(acc, m, l, q, cur, take, dv=dv,
                                     scale=0.1))
    # row 1 holds fewer than k: it keeps everything, itself too
    assert np.asarray(take).tolist() == [True, True, False]
    full = np.asarray(pool)[np.asarray(bt)][:, :, 0].reshape(b, -1, w)
    for r in range(b):
        sel = np.asarray(pos[r])[np.asarray(ok[r])]
        kv = np.concatenate([full[r], np.asarray(cur)[r][None]])
        idx = np.where(sel == lens[r], len(full[r]), sel)
        s = np.asarray(q[r]) @ kv[idx].T * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ kv[idx][:, :dv]
        np.testing.assert_allclose(got[r], want, rtol=1e-4, atol=1e-5)


# (5) the expert share --------------------------------------------------------

def test_the_shares_add_up():
    """Four chips of two experts each, the shared expert counted once:
    together the uncut layer."""
    uncut = dataclasses.replace(CFG, n_routed_experts=8, held_experts=None,
                                first_expert=0)
    p = ds.init_params(uncut, seed=11, dtype=jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], p["layers"])
    full = jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[2:]),
                                  p["experts"])
    h = jax.random.normal(jax.random.PRNGKey(2), (13, CFG.hidden_size))
    live = jnp.ones(13, bool)
    with jax.default_matmul_precision("highest"):
        want, _, idx = ds.expert_layer(lp, full, 0, h, live, uncut)
        gu = h @ p["experts"]["w_gate_up"][0, 8]
        i = CFG.moe_intermediate_size
        shared = (jax.nn.silu(gu[:, :i]) * gu[:, i:]) \
            @ p["experts"]["w_down"][0, 8]
        total, here = 0.0, 0
        for first in range(0, 8, 2):
            share = dataclasses.replace(uncut, first_expert=first,
                                        held_experts=2)
            mine = {k: jnp.concatenate([v[:, first:first + 2], v[:, 8:]],
                                       1).reshape((-1,) + v.shape[2:])
                    for k, v in p["experts"].items()}
            out, stats, chosen = ds.expert_layer(lp, mine, 0, h, live, share)
            assert (np.asarray(chosen) == np.asarray(idx)).all()
            total = total + (out - shared)
            here += int(stats[0])
    assert here == 13 * CFG.num_experts_per_tok
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-5)


# (6) what a Kanana-shaped configuration builds -------------------------------

def test_kanana_shape_builds_no_index_pool_and_no_selection():
    kanana = ds.DeepseekConfig.tiny()
    assert page_classes_of(ds, kanana) == [
        PageClass("latent", 3, 1, kanana.latent_width, None)]
    assert "index_ctx_tokens_total" not in ds.host_step_stats(
        kanana, np.asarray([3, 4]))
    assert ds.host_prefill_stats(kanana, 10, 16) == {}
    p = ds.init_params(kanana, seed=0, dtype=jnp.float32)
    pool = jnp.zeros((3, 9, 1, 16, kanana.latent_width), jnp.float32)
    text = str(jax.make_jaxpr(lambda p, pool: ds.paged_decode_step(
        p, kanana, pool, None, jnp.zeros((2, 4), jnp.int32),
        jnp.asarray([3, 0], jnp.int32), jnp.asarray([1, 2], jnp.int32),
        page=16))(p, pool))
    assert "latent_attention" in text or "dot_general" in text
    for name in ("dsa_select", "index_scores", "sparse_latent"):
        assert name not in text
    # one top_k, the router's in the scan over the expert layers, and
    # nothing else sorts
    assert text.count("top_k") == 1


def test_glm5_declares_the_index_pool_beside_the_latent_pool():
    (cls,) = page_classes_of(ds, CFG)
    assert cls == PageClass("latent", 3, 1, CFG.latent_width, 16)
    k, v = cls.pools(5, 16, jnp.bfloat16)
    assert k.shape == (3, 5, 1, 16, 128) and v.shape == (3, 5, 1, 16, 16)
    stats = ds.host_step_stats(CFG, np.asarray([3, 40]))
    assert stats["index_ctx_tokens_total"] == (4 + 41) * 3
    assert stats["sparse_selected_tokens_total"] == (4 + 16) * 3
    assert stats["index_key_bytes_total"] == 43 * 3 * 16 * 2
    assert stats["sparse_latent_bytes_total"] == 20 * 3 * 40 * 2
    assert ds.host_prefill_stats(CFG, 10, 16) == {
        "prefill_index_pairs_total": 55 * 3}


def test_from_hf_config_takes_glm5s_keys():
    hf = {"model_type": "glm_moe_dsa", "hidden_size": 6144,
          "q_lora_rank": 2048, "index_topk": 2048, "index_n_heads": 32,
          "index_head_dim": 128, "indexer_rope_interleave": True,
          "n_routed_experts": 16, "published": {"n_routed_experts": 256},
          "first_expert": 0, "rope_parameters": {"rope_theta": 1000000,
                                                 "rope_type": "default"},
          "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid"}
    cfg = ds.DeepseekConfig.from_hf_config(hf)
    assert (cfg.q_lora_rank, cfg.index_topk, cfg.n_routed_experts,
            cfg.held_experts, cfg.routed_held, cfg.rope_theta) == (
        2048, 2048, 256, 16, 16, 1e6)
    with pytest.raises(NotImplementedError):
        ds.DeepseekConfig.from_hf_config(
            {**hf, "rope_parameters": {"rope_type": "yarn"}})


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
def test_wide_expert_split_over_its_intermediate(activation):
    """An expert too wide for two buffers of its weights (GLM-5's) is
    taken a slice of its intermediate at a time: the same product as
    the XLA twin, row tiles of several experts and unused tiles too."""
    from unittest import mock

    from bigdl_tpu.llm.kernels import moe
    rs = np.random.RandomState(3)
    g, h, i, tm = 3, 128, 512, 16
    first = 2 * i if activation == "swiglu" else i
    x = jnp.asarray(rs.randn(5 * tm, h), jnp.float32)
    wgu = jnp.asarray(rs.randn(g, h, first) / np.sqrt(h), jnp.float32)
    wd = jnp.asarray(rs.randn(g, i, h) / np.sqrt(i), jnp.float32)
    tg = jnp.asarray([2, 0, 0, 1, 1], jnp.int32)
    n = jnp.asarray(4, jnp.int32)
    want = moe.moe_expert_ffn_reference(x, wgu, wd, tg, n, tm=tm,
                                        activation=activation)
    jax.clear_caches()
    with mock.patch.object(moe, "WHOLE_EXPERT_VMEM", 0), \
            mock.patch.object(moe, "SPLIT_EXPERT_VMEM", 2 * 3 * h * 128 * 4):
        got = moe.moe_expert_ffn(x, wgu, wd, tg, n, tm=tm, interpret=True,
                                 activation=activation)
    jax.clear_caches()
    np.testing.assert_allclose(np.asarray(got)[:4 * tm],
                               np.asarray(want)[:4 * tm], rtol=2e-5,
                               atol=2e-5)
