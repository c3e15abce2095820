"""The ``brumby`` family (power retention: a state of fixed size a row
and no cached token) against its plain float32 reference
(``tests/brumby_reference.py``, the attention form), at tiny widths on
the CPU: ``phi``, the three forms of one layer, the dense forward,
prefill then decode through the engine's state class, each kernel
against its XLA twin, the state ledger, the declaration, the refusals.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import brumby_reference as ref
from bigdl_tpu.llm.kernels import retention
from bigdl_tpu.llm.kvcache.classes import (PageClass, StateClass,
                                           StateLedger, every_token_class,
                                           page_classes_of)
from bigdl_tpu.llm.models import brumby, llama, mimo
from bigdl_tpu.llm.serving import LLMServer

CFG = brumby.BrumbyConfig.tiny()


@pytest.fixture(autouse=True, scope="module")
def _leave_no_executables():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params32():
    # the gates as the benchmark draws them: gamma in about 0.9 .. 0.999
    return brumby.init_params(CFG, seed=3, dtype=jnp.float32)


def _model(params, cfg=CFG):
    return brumby.BrumbyForCausalLM(cfg, params, max_cache_len=512)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


def _layer_inputs(t, hkv=2, grp=3, n=8, dv=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (t, hkv, grp, n)),
            jax.random.normal(ks[1], (t, hkv, n)),
            jax.random.normal(ks[2], (t, hkv, dv)),
            jax.nn.log_sigmoid(4.5 + 0.5 * jax.random.normal(
                ks[3], (t, hkv))))


def _attention_form(q, k, v, g, eps=1e-6):
    t, n = q.shape[0], q.shape[-1]
    run = jnp.cumsum(g, 0)
    decay = jnp.where(jnp.tril(jnp.ones((t, t), bool))[..., None],
                      jnp.exp(run[:, None] - run[None, :]), 0.0)
    a = jnp.einsum("thgn,shn->tshg", q, k) ** 2 / n * decay[..., None]
    return jnp.einsum("tshg,shv->thgv", a, v) / (a.sum(1) + eps)[..., None]


# (1) phi and the three forms of one layer ---------------------------------

@pytest.mark.parametrize("n", [2, 8, 128])
def test_phi_squares_the_dot_product(n):
    x, y = jax.random.normal(jax.random.PRNGKey(n), (2, 5, n))
    assert retention.phi(x).shape == (5, retention.state_width(n))
    np.testing.assert_allclose(
        (retention.phi(x) * retention.phi(y)).sum(-1), (x * y).sum(-1) ** 2,
        rtol=2e-5, atol=1e-5)
    # 8,256 products and 64 zeros the layout pads, at a head of 128
    assert int((np.asarray(retention.phi(x)) != 0).sum(-1).max()) \
        <= n * (n + 1) // 2


def test_recurrent_form_is_the_attention_form():
    q, k, v, g = _layer_inputs(21)
    p = retention.state_width(8)
    state, z = jnp.full((3, 2, 4, p), 7.0), jnp.full((3, 2, p), 7.0)
    state, z = state.at[2].set(0), z.at[2].set(0)
    ys = []
    for t in range(21):
        # batch row 0 is dead and names the trash row; row 1 is live
        two = lambda a: jnp.stack([jnp.ones_like(a[t]), a[t]])
        y, state, z = retention.retention_decode(
            state, z, two(q), two(k), two(v), two(g),
            jnp.asarray([0, 2], jnp.int32), jnp.asarray([False, True]))
        ys.append(y[1])
    np.testing.assert_allclose(jnp.stack(ys), _attention_form(q, k, v, g),
                               rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(state[1] - 7).max()) == 0      # nobody's row


@pytest.mark.parametrize("chunk,sub", [(8, 4), (8, 8), (7, 7), (21, 3),
                                       (32, 16)])
def test_chunked_form_is_the_attention_form(chunk, sub):
    """Chunks that do and do not divide the 21 positions; the state
    carried from chunk to chunk ends where the recurrent form's does."""
    q, k, v, g = _layer_inputs(21, seed=1)
    p = retention.state_width(8)
    state, z = jnp.full((3, 2, 4, p), 7.0), jnp.full((3, 2, p), 7.0)
    outs = []
    for c0 in range(0, 21, chunk):
        live = min(chunk, 21 - c0)
        pad = lambda a: jnp.pad(a[c0:c0 + live], [(0, chunk - live)] + [
            (0, 0)] * (a.ndim - 1), constant_values=3.0)
        y, state, z = retention.retention_prefill_chunk(
            state, z, pad(q), pad(k), pad(v), pad(g), jnp.int32(2),
            c0 == 0, jnp.int32(live), sub=sub)
        outs.append(y[:live])
    np.testing.assert_allclose(jnp.concatenate(outs),
                               _attention_form(q, k, v, g),
                               rtol=2e-4, atol=2e-5)
    _, want_s, want_z = retention.retention_dense(
        jnp.zeros((2, 4, p)), jnp.zeros((2, p)), q, k, v, g, sub=21)
    np.testing.assert_allclose(state[2], want_s, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(z[2], want_z, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(state[1] - 7).max()) == 0


# (2) the dense forward ------------------------------------------------------

@pytest.mark.parametrize("n", [1, 23, 70])
def test_dense_forward_matches_reference(params32, n):
    ids = _ids(n)
    logits, _ = _model(params32)(jnp.asarray(ids)[None])
    want = ref.brumby_logits(CFG, params32, ids)
    np.testing.assert_allclose(np.asarray(logits[0]), want,
                               rtol=1e-4, atol=1e-4 * np.abs(want).max())


def test_dense_forward_token_by_token_is_the_same(params32):
    ids = _ids(37, seed=2)
    model = _model(params32)
    logits, cache = model(jnp.asarray(ids[None, :20]))
    rows = [np.asarray(logits[0])]
    for t in range(20, 37):
        logits, cache = model(jnp.asarray(ids[None, t:t + 1]), cache=cache)
        rows.append(np.asarray(logits[0]))
    want = ref.brumby_logits(CFG, params32, ids)
    np.testing.assert_allclose(np.concatenate(rows), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    # the state the dense forward ends with is the sum the reference's
    # own keys, values and gates give
    kept = []
    ref.brumby_logits(CFG, params32, ids, rows=kept)
    want_s, _ = ref.state_of(*kept[0])
    from benchmark.drivers.serve_brumby import unpack_state
    got = unpack_state(np.asarray(cache["s"][0, 0]), CFG.head_dim)
    np.testing.assert_allclose(got.transpose(0, 2, 3, 1), want_s,
                               rtol=1e-3, atol=1e-4 * np.abs(want_s).max())


def test_from_hf_config_refuses_what_it_lacks():
    hf = {"hidden_size": 64, "num_attention_heads": 6,
          "num_key_value_heads": 2, "head_dim": 8, "rope_theta": 1000000,
          "model_type": "brumby", "max_window_layers": 40}
    cfg = brumby.BrumbyConfig.from_hf_config(hf)
    assert (cfg.group, cfg.state_width, cfg.rope_theta) == (3, 40, 1e6)
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True),
                       ("use_sliding_window", True)):
        with pytest.raises(NotImplementedError, match=key):
            brumby.BrumbyConfig.from_hf_config({**hf, key: value})


# (3) the engine: prefill in chunks, decode, slots reused ---------------------

def _served_is_reference_argmax(params, prompt, served):
    ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    want = ref.brumby_logits(CFG, params, ids)[len(prompt) - 1:]
    assert (want.argmax(-1) == np.asarray(served)).all()


def test_engine_serves_the_reference(params32):
    """Prompts of one to five prefill chunks (``prefill_chunk`` 16) and
    unequal lengths through TWO slots, so that each of the later ones is
    seated where a longer request's state lies: every served token is
    the float32 reference's argmax over the same ids."""
    srv = LLMServer(_model(params32), max_batch=2, max_seq_len=512,
                    page_size=16)
    (ledger,) = srv._states
    work = [(_ids(n, seed=n), new) for n, new in
            ((70, 12), (9, 30), (33, 8), (50, 6), (17, 9))]
    reqs = [srv.submit(p, max_new_tokens=new) for p, new in work]
    srv.start()
    try:
        for (prompt, new), req in zip(work, reqs):
            served = req.get(timeout=300)
            assert len(served) == new
            _served_is_reference_argmax(params32, prompt, served)
    finally:
        srv.stop()
    c = srv.step_counters
    assert srv.pass_errors == 0 and srv.pages_in_use == 0
    assert ledger.slots_in_use() == 0 and sum(ledger.seatings) == 5
    assert max(ledger.seatings) >= 2            # a slot was reused
    assert c["state_slots_zeroed_total"] == 5
    assert c["state_rows_total"] == c["decode_rows_total"] \
        == sum(new for _, new in work)          # a step a served token
    assert c["state_bytes_moved_total"] == c["state_rows_total"] \
        * brumby.state_bytes_a_row(CFG)
    assert c["prefill_state_chunks_total"] == CFG.num_hidden_layers * sum(
        -(-len(p) // CFG.prefill_chunk) for p, _ in work)
    assert c["prefill_state_positions_total"] \
        == c["prefill_state_chunks_total"] * CFG.prefill_chunk
    assert c["state_slots_held_total"] >= c["decode_rows_total"]


def test_a_reused_slot_starts_from_nothing(params32):
    """The mechanism's characteristic bug: the same request served
    first in a fresh engine and then in a slot that a longer request
    has just left gives the same tokens; with the zeroing planted out
    it does not."""
    prompt, long_one = _ids(20, seed=5), _ids(90, seed=6)

    def served_second(plant):
        with plant:
            srv = LLMServer(_model(params32), max_batch=1, max_seq_len=256)
            srv.start()
            try:
                srv.submit(long_one, max_new_tokens=4).get(timeout=300)
                return srv.submit(prompt, max_new_tokens=10).get(timeout=300)
            finally:
                srv.stop()

    import contextlib

    from benchmark import faults_brumby
    clean = served_second(contextlib.nullcontext())
    _served_is_reference_argmax(params32, prompt, clean)
    assert clean != served_second(faults_brumby.planted("slot_not_zeroed"))


# (4) each kernel against its XLA twin, interpret mode ------------------------

def _kernel_inputs(b, n=128, dv=128, hkv=2, grp=2):
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    unit = lambda x: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    p = retention.state_width(n)
    return dict(
        state=jax.random.normal(ks[0], (b + 2, hkv, dv, p)),
        z=30 + jnp.abs(jax.random.normal(ks[1], (b + 2, hkv, p))),
        q=unit(jax.random.normal(ks[2], (b, hkv, grp, n))),
        k=unit(jax.random.normal(ks[3], (b, hkv, n))),
        v=jax.random.normal(ks[4], (b, hkv, dv)),
        g=jax.nn.log_sigmoid(4 + jax.random.normal(ks[5], (b, hkv))))


def _decode_case(slots, grp):
    """The decode operands of ``len(slots)`` batch rows, a row live
    where its slot is not the trash row."""
    slots = jnp.asarray(slots, jnp.int32)
    a = _kernel_inputs(len(slots), grp=grp)
    return (a["state"], a["z"], a["q"], a["k"], a["v"], a["g"], slots,
            slots > 0)


@pytest.mark.parametrize("slots,grp,slab", [
    ((3, 0, 1), 2, 1664),       # ISSUE 33's: one dead row between two
    ((3, 0, 1), 5, 1664),       # Brumby's group: 5 query rows held as 8
    ((3, 0, 1), 5, retention.SLAB),     # ... at the slab the chip runs
    ((0, 5, 0, 0, 2, 0, 6), 2, retention.SLAB),     # ... a live row last
    ((0, 0, 0), 2, retention.SLAB),     # no live row at all
], ids=["one_dead", "group_5", "group_5_one_slab", "dead_between_live_last",
        "none_live"])
def test_decode_kernel_matches_its_twin(slots, grp, slab):
    args = _decode_case(slots, grp)
    want = retention._decode_xla(*args, 1e-6)
    got = retention.retention_decode(*args, slab=slab, interpret=True)
    rows = np.flatnonzero(np.asarray(slots))
    held = np.asarray(slots)[rows]
    # the read-out is a bfloat16 product, the update float32
    np.testing.assert_allclose(np.asarray(got[0])[rows],
                               np.asarray(want[0])[rows], atol=0.02)
    for g_, w_ in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g_)[held],
                                   np.asarray(w_)[held], rtol=1e-5,
                                   atol=1e-5)
    # the slots no live row names are not touched, bit for bit, in either
    # array (row 0 is the trash row: what it holds means nothing)
    idle = np.setdiff1d(np.arange(1, len(slots) + 2), held)
    for g_, old in zip(got[1:], args[:2]):
        assert np.array_equal(np.asarray(g_)[idle], np.asarray(old)[idle])


@pytest.mark.parametrize("hook,fault", [
    ("_phi_tile", "power_1"), ("SQRT2", "no_sqrt2"),
    ("_decay", "z_not_decayed"), ("_finish", "no_normaliser")])
def test_decode_kernel_reaches_a_hook_through_the_module(hook, fault):
    """ISSUE 36: ``benchmark/faults_brumby.py`` replaces four names of
    the module while the served program is traced (and drops what jit
    remembers). The kernel's body has to look each up in the module, so
    that the fault shows in what the KERNEL computes."""
    from benchmark import faults_brumby
    args = _decode_case((3, 0, 1), 5)
    clean = retention.retention_decode(*args, interpret=True)
    inner = getattr(retention, hook)
    with faults_brumby.planted(fault):
        assert getattr(retention, hook) is not inner
        faulty = retention.retention_decode(*args, interpret=True)
    rows = np.asarray([0, 2])
    assert float(np.abs(np.asarray(faulty[0])[rows]
                        - np.asarray(clean[0])[rows]).max()) > 1e-3


@pytest.mark.parametrize("fresh,n_live", [(True, 32), (False, 19)])
def test_prefill_kernel_matches_its_twin(fresh, n_live):
    a = _kernel_inputs(32)
    args = (a["state"][:4], a["z"][:4], a["q"], a["k"], a["v"], a["g"],
            jnp.int32(2), fresh, jnp.int32(n_live))
    want = retention.retention_prefill_chunk(*args, sub=16)
    got = retention.retention_prefill_chunk(*args, sub=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got[0])[:n_live],
                               np.asarray(want[0])[:n_live], atol=0.03)
    scale = float(jnp.abs(want[1][2]).max())
    np.testing.assert_allclose(got[1][2], want[1][2], atol=0.01 * scale)
    np.testing.assert_allclose(got[2][2], want[2][2], rtol=1e-4, atol=1e-5)
    for row in (0, 1, 3):
        assert float(jnp.abs(got[1][row] - a["state"][row]).max()) == 0


# (5) the state class, its ledger, the declaration ----------------------------

def test_a_state_classes_ledger_balances():
    cls = StateClass("state", 2, 2, 40, 8)
    s, z = cls.arrays(3)
    assert s.shape == (2, 4, 2, 8, 40) and z.shape == (2, 4, 2, 40)
    assert s.dtype == z.dtype == jnp.float32
    assert cls.slot_bytes == 2 * 2 * (8 + 1) * 40 * 4
    ledger = StateLedger(cls, 3)
    assert ledger.rows.tolist() == [[1], [2], [3]]      # row 0: trash
    assert ledger.seat(1) == 2 and ledger.seat(0) == 1
    assert ledger.slots_in_use() == 2
    assert ledger.bytes_held() == 2 * cls.slot_bytes
    with pytest.raises(ValueError, match="seated already"):
        ledger.seat(1)
    assert ledger.release(1) == 1 and ledger.release(1) == 0
    assert ledger.seat(1) == 2 and ledger.seatings == [1, 2, 0]
    assert ledger.release(0) + ledger.release(1) == 2
    assert ledger.slots_in_use() == 0 and ledger.bytes_held() == 0


def test_a_family_may_keep_no_token():
    (state,) = page_classes_of(brumby, CFG)
    assert state == StateClass("state", 2, 2, 40, 8)
    assert every_token_class([state]) is None
    lc = llama.LlamaConfig.tiny()
    assert every_token_class(page_classes_of(llama, lc)).name == "kv"
    assert every_token_class(page_classes_of(
        mimo, mimo.MimoConfig.tiny())).name == "full"


@pytest.mark.parametrize("declared", [
    [PageClass("a", 1, 1, 8, 8), PageClass("b", 1, 1, 8, 8)],
    [PageClass("w", 1, 1, 8, 8, keeps=16), PageClass("a", 1, 1, 8, 8)],
    [StateClass("s", 1, 1, 40, 8), PageClass("a", 1, 1, 8, 8)],
    []])
def test_at_most_one_class_keeps_every_token_and_it_comes_first(declared):
    class Fam:
        __name__ = "fam"
        page_classes = staticmethod(lambda cfg: declared)
    with pytest.raises(ValueError, match="at most one class keeps every"):
        page_classes_of(Fam, None)


def test_page_classes_beside_a_state_class_are_taken():
    class Fam:
        __name__ = "fam"
        page_classes = staticmethod(lambda cfg: [
            PageClass("full", 1, 1, 8, 8),
            PageClass("window", 1, 1, 8, 8, keeps=16),
            StateClass("state", 1, 1, 40, 8)])
    assert [c.name for c in page_classes_of(Fam, None)] == [
        "full", "window", "state"]


@pytest.mark.parametrize("feature", ["kvcache", "kvtier", "mixed", "spec",
                                     "priority"])
def test_what_moves_pages_refuses_a_state_class(params32, feature):
    with pytest.raises(NotImplementedError,
                       match=r"1 class \(state\), state a slot holds.*"
                             r"a state class"):
        LLMServer(_model(params32), max_batch=2, max_seq_len=64,
                  **{feature: True})


def test_num_pages_is_not_asked_of_a_family_that_keeps_no_token(params32):
    srv = LLMServer(_model(params32), max_batch=3,
                    max_seq_len=10 ** 6, num_pages=5)
    assert srv.max_seq_len == CFG.max_position_embeddings
    assert srv._k_pages.shape == (2, 4, 2, 8, 40)       # the state arrays
    assert srv._tables().shape == (3, 1)
    # a request as long as the model allows is admitted by slot alone
    assert srv._kv.peek(_ids(2000), 40)["pages_needed"] == 0


def test_the_two_copies_of_the_reference_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "brumby_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmark",
                           "reference_brumby.py")) as f:
        theirs = f.read()
    assert mine == theirs
