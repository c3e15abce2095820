"""The ``nemotron_h`` family (one mixer a layer: Mamba-2, attention or
LatentMoE; a page class AND a state class in one engine) against its
plain float32 reference (``tests/nemotron_h_reference.py``, the dual
form), at tiny widths on the CPU: the three forms of one Mamba-2 layer,
the dense forward, prefill then decode through the engine's two classes,
each kernel against its XLA twin, the shares of an expert layer, the
declaration, the refusals.
"""

import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nemotron_h_reference as ref
from bigdl_tpu.llm.kernels import ssm
from bigdl_tpu.llm.kvcache.classes import (PageClass, StateClass,
                                           page_classes_of)
from bigdl_tpu.llm.models import nemotron_h as nh
from bigdl_tpu.llm.serving import LLMServer

CFG = nh.NemotronHConfig.tiny()


@pytest.fixture(autouse=True, scope="module")
def _leave_no_executables():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params32():
    return nh.init_params(CFG, seed=3, dtype=jnp.float32)


def _model(params, cfg=CFG):
    return nh.NemotronHForCausalLM(cfg, params, max_cache_len=512,
                                   cache_dtype=jnp.float32)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


def _layer_inputs(t, heads=4, groups=2, p=4, n=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (t, heads, p)),
        bm=jax.random.normal(ks[1], (t, groups, n)),
        cm=jax.random.normal(ks[2], (t, groups, n)),
        dt=jax.nn.softplus(jax.random.normal(ks[3], (t, heads)) - 3.0),
        a=-jnp.exp(jax.random.uniform(ks[4], (heads,), minval=0.0,
                                      maxval=2.5)),
        d=jax.random.normal(ks[5], (heads,)))


def _dual_form(x, bm, cm, dt, a, d):
    t, heads = dt.shape
    hpg = heads // bm.shape[1]
    run = jnp.cumsum(dt, 0) * a
    decay = jnp.where(jnp.tril(jnp.ones((t, t), bool))[..., None],
                      jnp.exp(run[:, None] - run[None, :]), 0.0)
    band = jnp.repeat(jnp.einsum("tgn,sgn->tsg", cm, bm), hpg, axis=-1)
    return jnp.einsum("tsh,shp->thp", band * decay, dt[..., None] * x) \
        + d[None, :, None] * x


# (1) the three forms of one Mamba-2 layer ----------------------------------

def test_recurrent_form_is_the_dual_form():
    a = _layer_inputs(21)
    state = jnp.full((3, 4, 4, 8), 7.0).at[2].set(0)
    ys = []
    for t in range(21):
        # batch row 0 is dead and names the trash row; row 1 is live
        two = lambda v: jnp.stack([jnp.ones_like(v[t]), v[t]])
        y, state = ssm.ssm_decode(
            state, two(a["x"]), two(a["bm"]), two(a["cm"]), two(a["dt"]),
            a["a"], a["d"], jnp.asarray([0, 2], jnp.int32),
            jnp.asarray([False, True]))
        ys.append(y[1])
        assert float(jnp.abs(y[0]).max()) == 0      # a dead row reads zero
    np.testing.assert_allclose(jnp.stack(ys), _dual_form(**a), rtol=2e-4,
                               atol=2e-5)
    assert float(jnp.abs(state[1] - 7).max()) == 0      # nobody's row


@pytest.mark.parametrize("chunk,sub", [(8, 4), (8, 8), (7, 7), (21, 3),
                                       (32, 16)])
def test_chunked_form_is_the_dual_form(chunk, sub):
    """Chunks that do and do not divide the 21 positions (a boundary
    inside the prompt and at its end); the state carried from chunk to
    chunk ends where the recurrent form's does."""
    a = _layer_inputs(21, seed=1)
    state = jnp.full((3, 4, 4, 8), 7.0)
    outs = []
    for c0 in range(0, 21, chunk):
        live = min(chunk, 21 - c0)
        pad = lambda v: jnp.pad(v[c0:c0 + live], [(0, chunk - live)] + [
            (0, 0)] * (v.ndim - 1), constant_values=3.0)
        y, state = ssm.ssd_prefill_chunk(
            state, pad(a["x"]), pad(a["bm"]), pad(a["cm"]), pad(a["dt"]),
            a["a"], a["d"], jnp.int32(2), c0 == 0, jnp.int32(live), sub=sub)
        outs.append(y[:live])
    np.testing.assert_allclose(jnp.concatenate(outs), _dual_form(**a),
                               rtol=2e-4, atol=2e-5)
    _, want = ssm.ssd_dense(jnp.zeros((4, 4, 8)), a["x"], a["bm"], a["cm"],
                            a["dt"], a["a"], a["d"], sub=21)
    np.testing.assert_allclose(state[2], want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(state[1] - 7).max()) == 0


# (2) the dense forward against the reference ---------------------------------

def _dense_logits(params, ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        logits, cache = nh.forward(
            params, cfg, jnp.asarray(ids)[None],
            nh.init_cache(cfg, 1, len(ids), jnp.float32),
            jnp.arange(len(ids))[None])
    return np.asarray(logits[0]), cache


@pytest.mark.parametrize("n", [1, 23, 70])
def test_dense_forward_matches_reference(params32, n):
    ids = _ids(n, seed=n)
    got, cache = _dense_logits(params32, ids)
    rows = []
    want, _ = ref.nemotron_h_logits(CFG, params32, ids, rows=rows)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # what the recurrence holds at the end is what the reference builds
    # directly: the state, and the convolution's last inputs
    held = [r for r in rows if r[0] == "M"]
    for i, (_, state, window) in enumerate(held):
        np.testing.assert_allclose(cache["ssm"][i, 0], state, rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(cache["conv"][i, 0, -min(n, 3):],
                                   window, rtol=2e-4, atol=2e-5)


def test_dense_forward_token_by_token_is_the_same(params32):
    ids = _ids(19, seed=4)
    whole, _ = _dense_logits(params32, ids)
    cache = nh.init_cache(CFG, 1, 32, jnp.float32)
    step = jax.jit(lambda tok, cache, at: nh.forward(
        params32, CFG, tok, cache, at))
    rows = []
    with jax.default_matmul_precision("highest"):
        for t, tok in enumerate(ids):
            logits, cache = step(jnp.asarray([[tok]]), cache,
                                 jnp.asarray([[t]]))
            rows.append(np.asarray(logits[0, 0]))
    np.testing.assert_allclose(np.stack(rows), whole, rtol=2e-4, atol=2e-4)


def test_from_hf_config_reads_the_pattern_and_the_share():
    hf = dict(hybrid_override_pattern="MEMEMEM*EME", num_hidden_layers=11,
              n_routed_experts=128, first_expert=0,
              published={"n_routed_experts": 512, "num_hidden_layers": 88},
              norm_eps=1e-5, layer_norm_epsilon=1e-5,
              num_nextn_predict_layers=1, rope_theta=10000,
              routed_scaling_factor=5, mlp_hidden_act="relu2")
    cfg = nh.NemotronHConfig.from_hf_config(hf)
    assert cfg.layers_of("M") == [0, 2, 4, 6, 9]
    assert cfg.layers_of("*") == [7] and cfg.num_moe_layers == 5
    assert (cfg.n_routed_experts, cfg.experts_held) == (512, 128)
    assert (cfg.d_inner, cfg.conv_dim) == (8192, 10240)
    assert sum(nh.in_proj_widths(cfg)) == 18560
    kv, state = nh.page_classes(cfg)
    assert kv == PageClass("kv", 1, 2, 128, 128)
    # 4.19 MB of float32 matrix and 61 KB of window a row and layer
    assert state.slot_bytes == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert nh.state_bytes_a_row(cfg) == 5 * 2 * 128 * 64 * 128 * 4


@pytest.mark.parametrize("key,value", [
    ("mamba_proj_bias", True), ("n_group", 2), ("mlp_hidden_act", "silu"),
    ("hybrid_override_pattern", "M-*E"), ("residual_in_fp32", True)])
def test_from_hf_config_refuses_what_it_lacks(key, value):
    hf = dict(hybrid_override_pattern="M*EE", num_hidden_layers=4,
              n_routed_experts=16)
    nh.NemotronHConfig.from_hf_config(hf)
    with pytest.raises(NotImplementedError, match="does not implement"):
        nh.NemotronHConfig.from_hf_config({**hf, key: value})


# (3) prefill then decode through the engine: pages AND state -----------------

def _served_is_reference_argmax(params, prompt, served):
    ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    want, _ = ref.nemotron_h_logits(CFG, params, ids)
    assert (want[len(prompt) - 1:].argmax(-1) == np.asarray(served)).all()


def _wait_idle(srv):
    import time
    deadline = time.perf_counter() + 60
    while not srv.engine_idle() and time.perf_counter() < deadline:
        time.sleep(0.01)


def test_engine_serves_the_reference(params32):
    """Prompts of one to three prefill chunks (``prefill_chunk`` 32) and
    unequal lengths through TWO slots, so that each of the later ones is
    seated where another request's state and window lie, its pages
    granted beside: every served token is the float32 reference's
    argmax over the same ids, and the ledgers of both classes balance."""
    srv = LLMServer(_model(params32), max_batch=2, max_seq_len=256,
                    page_size=16)
    (ledger,) = srv._states
    assert srv._multi and srv._every.name == "kv"
    work = [(_ids(n, seed=n), new) for n, new in
            ((70, 12), (9, 30), (33, 8), (50, 6), (17, 9))]
    reqs = [srv.submit(p, max_new_tokens=new) for p, new in work]
    srv.start()
    try:
        for (prompt, new), req in zip(work, reqs):
            served = req.get(timeout=300)
            assert len(served) == new
            _served_is_reference_argmax(params32, prompt, served)
        _wait_idle(srv)
    finally:
        srv.stop()
    c = srv.step_counters
    n_m, n_e = len(CFG.layers_of("M")), CFG.num_moe_layers
    assert srv.pass_errors == 0 and srv.pages_in_use == 0
    assert srv.pages_in_use_by_class == {"kv": 0}
    assert ledger.slots_in_use() == 0 and sum(ledger.seatings) == 5
    assert max(ledger.seatings) >= 2            # a slot was reused
    assert c["state_slots_zeroed_total"] == 5   # a request, not an array
    steps = sum(new for _, new in work)         # a step a served token
    assert c["ssm_rows_total"] == c["decode_rows_total"] == steps
    assert c["ssm_state_bytes_moved_total"] == steps \
        * nh.state_bytes_a_row(CFG)
    assert c["prefill_ssm_chunks_total"] == n_m * sum(
        -(-len(p) // CFG.prefill_chunk) for p, _ in work)
    assert c["state_slots_held_total"] >= c["decode_rows_total"]
    assert c["moe_token_layers_total"] == steps * n_e
    assert c["moe_assignments_total"] \
        + c["moe_assignments_elsewhere_total"] \
        == CFG.num_experts_per_tok * steps * n_e
    assert c["kv_ctx_tokens_total"] == sum(
        sum(range(len(p), len(p) + new)) for p, new in work)


def test_a_release_frees_the_pages_and_the_slot(params32):
    srv = LLMServer(_model(params32), max_batch=2, max_seq_len=128,
                    page_size=16)
    (ledger,) = srv._states
    free = len(srv._free)
    srv.start()
    try:
        req = srv.submit(_ids(40, seed=9), max_new_tokens=30)
        while len(req.tokens) < 3:
            req.done.wait(0.01)
        # held at once: the prompt's pages (and the one being filled)
        # and the slot's row
        assert srv.pages_in_use >= 3 and ledger.slots_in_use() == 1
        assert srv.state_slots_in_use == 1
        req.get(timeout=300)
        _wait_idle(srv)
    finally:
        srv.stop()
    assert srv.pages_in_use == 0 and len(srv._free) == free
    assert ledger.slots_in_use() == 0 and ledger.bytes_held() == 0


@pytest.mark.parametrize("fault", ["window_not_zeroed", "state_not_zeroed"])
def test_a_reused_slot_starts_from_nothing_in_both_arrays(params32, fault):
    """The mechanism's characteristic bug: the same request served
    first in a fresh engine and then in a slot that a longer request has
    just left computes the same logits; with the zeroing of EITHER array
    planted out it does not (a window left behind poisons only the first
    three positions: the prompt is short, so that they still count)."""
    from benchmark import faults_nemotron_h
    prompt, long_one = _ids(6, seed=5), _ids(90, seed=6)

    def served(plant, before=()):
        with plant:
            srv = LLMServer(_model(params32), max_batch=1, max_seq_len=256)
            srv.start()
            try:
                for other in before:
                    srv.submit(other, max_new_tokens=4).get(timeout=300)
                toks = srv.submit(prompt, max_new_tokens=10).get(timeout=300)
                _wait_idle(srv)
                return toks, np.asarray(srv._last[0])
            finally:
                srv.stop()

    first, first_row = served(contextlib.nullcontext())
    _served_is_reference_argmax(params32, prompt, first)
    second, second_row = served(contextlib.nullcontext(), [long_one])
    assert second == first
    np.testing.assert_allclose(second_row, first_row, atol=1e-4)
    _, faulty_row = served(faults_nemotron_h.planted(fault), [long_one])
    assert float(np.abs(faulty_row - first_row).max()) > 1e-2


# (4) each kernel against its XLA twin, interpret mode ------------------------

def _kernel_inputs(b, heads=32, groups=2, p=8, n=128, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        state=jax.random.normal(ks[0], (b + 2, heads, p, n)),
        x=jax.random.normal(ks[1], (b, heads, p)),
        bm=jax.random.normal(ks[2], (b, groups, n)) / 3,
        cm=jax.random.normal(ks[3], (b, groups, n)) / 3,
        dt=jax.nn.softplus(jax.random.normal(ks[4], (b, heads)) - 3.0),
        a=-jnp.exp(jax.random.uniform(ks[5], (heads,), maxval=2.5)),
        d=jax.random.normal(ks[6], (heads,)))


@pytest.mark.parametrize("slots,hb", [
    ([3, 0, 1, 0], 16), ([0, 2, 4], 32), ([0, 0], 32)])
def test_decode_kernel_matches_its_twin(slots, hb):
    """Live and dead rows in any order, one and two groups a block, no
    live row at all: the kernel's ``y`` and every live row's state are
    the twin's (the read-out to the bfloat16 of its MXU operands), and
    no row a live row does not name is touched."""
    slots = jnp.asarray(slots, jnp.int32)
    a = _kernel_inputs(len(slots))
    args = (a["state"], a["x"], a["bm"], a["cm"], a["dt"], a["a"], a["d"],
            slots, slots > 0)
    want_y, want_s = ssm.ssm_decode(*args)
    got_y, got_s = ssm.ssm_decode(*args, heads_block=hb, interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=0.02 * float(
        jnp.abs(want_y).max()) + 1e-6)
    for row in range(1, a["state"].shape[0]):
        if row in slots.tolist():
            np.testing.assert_allclose(got_s[row], want_s[row], rtol=1e-5,
                                       atol=1e-5)
        else:
            assert float(jnp.abs(got_s[row] - a["state"][row]).max()) == 0


@pytest.mark.parametrize("fresh,n_live", [(True, 256), (False, 200)])
def test_prefill_kernel_matches_its_twin(fresh, n_live):
    a = _kernel_inputs(256, seed=8)
    args = (a["state"][:5], a["x"], a["bm"], a["cm"], a["dt"], a["a"],
            a["d"], jnp.int32(2), fresh, jnp.int32(n_live))
    want_y, want_s = ssm.ssd_prefill_chunk(*args)
    got_y, got_s = ssm.ssd_prefill_chunk(*args, interpret=True)
    scale = float(jnp.abs(want_y[:n_live]).max())
    np.testing.assert_allclose(got_y[:n_live], want_y[:n_live],
                               atol=0.01 * scale)
    np.testing.assert_allclose(got_s[2], want_s[2], atol=0.01 * float(
        jnp.abs(want_s[2]).max()))
    for row in (0, 1, 3, 4):
        assert float(jnp.abs(got_s[row] - a["state"][row]).max()) == 0


def test_both_kernels_reach_the_hook_through_the_module():
    """``_held`` is what a planted fault replaces: the kernels' bodies
    and their twins must read the module's global when they are traced."""
    from unittest import mock
    a = _kernel_inputs(2)
    slots = jnp.asarray([1, 2], jnp.int32)
    args = (a["state"], a["x"], a["bm"], a["cm"], a["dt"], a["a"], a["d"],
            slots, slots > 0)
    clean = [ssm.ssm_decode(*args)[1], ssm.ssm_decode(*args,
                                                      interpret=True)[1]]
    jax.clear_caches()
    with mock.patch.object(ssm, "_held", lambda s: 0.0 * s):
        for interpret in (None, True):
            zeroed = ssm.ssm_decode(*args, interpret=interpret)[1]
            assert float(jnp.abs(zeroed[1:3]).max()) == 0
    jax.clear_caches()
    assert float(jnp.abs(clean[0][1:3]).max()) > 0


# (5) the expert layer's shares ----------------------------------------------

def test_the_shares_add_up(params32):
    """Four chips, each holding a quarter of the experts, each applying
    the latent up-projection to its own partial sum, and the shared
    expert counted once: together the uncut reference's layer."""
    uncut = dataclasses.replace(CFG, first_expert=0,
                                experts_held=CFG.n_routed_experts)
    lp = nh.init_params(uncut, seed=11, dtype=jnp.float32)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (13, CFG.hidden_size))
    want, idx, _, h = ref._expert_layer(
        x, lp, first=0, top_k=CFG.num_experts_per_tok,
        scaling=float(CFG.routed_scaling_factor), norm_topk=True,
        eps=CFG.layer_norm_epsilon)
    live = jnp.ones(13, bool)
    total, here = 0.0, 0
    with jax.default_matmul_precision("highest"):
        shared = nh.relu2_mlp(lp["shared_up"], lp["shared_down"], h)
        for first in range(0, CFG.n_routed_experts, 4):
            share = dataclasses.replace(CFG, first_expert=first,
                                        experts_held=4)
            mine = {**lp, "experts": {k: v[first:first + 4]
                                      for k, v in lp["experts"].items()}}
            out, stats, chosen = nh.expert_mixer(mine, h, live, share)
            assert (np.sort(chosen, -1) == np.sort(idx, -1)).all()
            total = total + (out - shared)
            here += int(stats[0])
    assert here == 13 * CFG.num_experts_per_tok
    np.testing.assert_allclose(x + total + shared, want, rtol=2e-4,
                               atol=2e-5)


# (6) the declaration, the classes, the refusals -----------------------------

def test_a_page_class_beside_a_state_class():
    kv, state = page_classes_of(nh, CFG)
    assert kv == PageClass("kv", 1, 2, 16, 16)
    assert state == StateClass("ssm", 2, holds=(
        ("state", (8, 8, 16), "float32"), ("conv", (3, 128), "bfloat16")))
    s, w = state.arrays(3)
    assert s.shape == (2, 4, 8, 8, 16) and s.dtype == jnp.float32
    assert w.shape == (2, 4, 3, 128) and w.dtype == jnp.bfloat16
    assert state.slot_bytes == 2 * (8 * 8 * 16 * 4 + 3 * 128 * 2)


def test_a_retention_declaration_builds_what_it_built():
    """``StateClass`` once WAS retention's shape (heads, rows, width):
    that form is a shorthand now, and the same two arrays come from it,
    from naming them, and at another precision from ``replace``."""
    cls = StateClass("state", 2, 2, 40, 8)
    assert cls == StateClass("state", 2, 2, 40, 8, holds=(
        ("state", (2, 8, 40), "float32"), ("z", (2, 40), "float32")))
    s, z = cls.arrays(3)
    assert s.shape == (2, 4, 2, 8, 40) and z.shape == (2, 4, 2, 40)
    assert s.dtype == z.dtype == jnp.float32
    assert cls.slot_bytes == 2 * 2 * (8 + 1) * 40 * 4
    named = StateClass("state", 2, holds=cls.holds)
    assert [a.shape for a in named.arrays(3)] == [s.shape, z.shape]
    assert named.slot_bytes == cls.slot_bytes
    half = dataclasses.replace(cls, dtype="bfloat16")
    assert half.arrays(1)[0].dtype == jnp.bfloat16
    assert half.slot_bytes == cls.slot_bytes // 2
    one = StateClass("s", 1, holds=(("only", (4,), "float32"),))
    assert one.arrays(1)[1] is None and one.slot_bytes == 16


def test_a_state_class_names_one_or_two_arrays():
    with pytest.raises(ValueError, match="one or\\s+two"):
        StateClass("s", 1, holds=tuple((n, (4,), "float32")
                                       for n in "abc"))
    with pytest.raises(ValueError, match="names 0 arrays"):
        StateClass("s", 1)


@pytest.mark.parametrize("feature", ["kvcache", "kvtier", "mixed", "spec",
                                     "priority"])
def test_what_moves_pages_refuses_the_state_class_by_name(params32,
                                                          feature):
    with pytest.raises(NotImplementedError,
                       match=r"2 classes \(kv, ssm\), state a slot holds.*"
                             r"a state class"):
        LLMServer(_model(params32), max_batch=2, max_seq_len=64,
                  **{feature: True})


def test_the_two_copies_of_the_reference_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "nemotron_h_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmark",
                           "reference_nemotron_h.py")) as f:
        theirs = f.read()
    assert mine == theirs
