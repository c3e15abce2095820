"""Pallas kernels executed on the real chip with production tile sizes
and real (non-interpret) Mosaic lowering.

The r2 kernel lowered only under ``interpret=True`` with toy tiles, so
its illegal scale BlockSpec survived two rounds of green tests while the
flagship bench errored on hardware. These tests pin the actual lowering.
"""

import functools

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.llm.ggml.quantize import dequantize, quantize
from bigdl_tpu.llm.kernels import (
    asym_int4_matmul, int4_matmul, int4_matmul_reference, int8_matmul,
    to_tpu_layout)


def _rand_quant(n, k, qtype, seed=0):
    rs = np.random.RandomState(seed)
    w = rs.randn(n, k).astype(np.float32) * 0.05
    qd = quantize(w, qtype)
    return w, qd, to_tpu_layout(qd)


class TestInt4OnChip:
    def _check(self, m, n, k, mode="auto"):
        w, qd, td = _rand_quant(n, k, "sym_int4")
        rs = np.random.RandomState(1)
        x = rs.randn(m, k).astype(np.float32)
        ref = int4_matmul_reference(x, qd["q"], qd["scale"])
        out = np.asarray(int4_matmul(
            jnp.asarray(x, jnp.bfloat16), jnp.asarray(td["q"]),
            jnp.asarray(td["scale"]), out_dtype=jnp.float32, mode=mode),
            np.float32)
        rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-6)
        assert rel < 0.03, f"m={m} n={n} k={k} mode={mode}: rel={rel}"

    def test_decode_matvec_llama_ffn(self):
        """(1, 4096) @ (11008, 4096) — the 7B decode hot shape."""
        self._check(1, 11008, 4096)

    def test_decode_matvec_down_proj(self):
        """K=11008 is not 128*QK-aligned — exercises the full-K scale
        block path that broke the r2 kernel."""
        self._check(1, 4096, 11008)

    def test_prefill_sub8_mode(self):
        self._check(512, 4096, 4096, mode="sub8")

    def test_corr_mode(self):
        self._check(16, 4096, 4096, mode="corr")

    def test_unaligned_n(self):
        """N not a multiple of bn — exercises N padding."""
        self._check(3, 1000, 256)

    @pytest.mark.parametrize("n,k", [(4096, 14336), (28672, 4096),
                                     (6144, 4096)])
    def test_mistral_7b_widths(self, n, k):
        """The linears chip_smoke.py serves (``LlamaConfig.mistral_7b``,
        fused layout): down_proj K=14336 (two 7168-row K chunks), fused
        gate_up N=28672, fused qkv N=6144 — at decode (m=1, corr mode)
        and at the largest prefill bucket (m=2048, sub8 mode), one
        weight set for both."""
        w, qd, td = _rand_quant(n, k, "sym_int4")
        q, scale = jnp.asarray(td["q"]), jnp.asarray(td["scale"])
        rs = np.random.RandomState(1)
        for m in (1, 2048):
            x = rs.randn(m, k).astype(np.float32)
            ref = int4_matmul_reference(x, qd["q"], qd["scale"])
            out = np.asarray(int4_matmul(
                jnp.asarray(x, jnp.bfloat16), q, scale,
                out_dtype=jnp.float32), np.float32)
            rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-6)
            assert rel < 0.03, f"m={m} n={n} k={k}: rel={rel}"


class TestOtherKernelsOnChip:
    def test_int8(self):
        w, qd, td = _rand_quant(512, 1024, "sym_int8")
        rs = np.random.RandomState(2)
        x = rs.randn(8, 1024).astype(np.float32)
        ref = x @ dequantize(qd).T
        out = np.asarray(int8_matmul(
            jnp.asarray(x, jnp.bfloat16), jnp.asarray(td["q"]),
            jnp.asarray(td["scale"]), out_dtype=jnp.float32), np.float32)
        rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-6)
        assert rel < 0.03, rel

    def test_asym_int4(self):
        w, qd, td = _rand_quant(512, 1024, "asym_int4")
        rs = np.random.RandomState(3)
        x = rs.randn(8, 1024).astype(np.float32)
        ref = x @ dequantize(qd).T
        out = np.asarray(asym_int4_matmul(
            jnp.asarray(x, jnp.bfloat16), jnp.asarray(td["q"]),
            jnp.asarray(td["scale"]), jnp.asarray(td["zero"]),
            out_dtype=jnp.float32), np.float32)
        rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-6)
        assert rel < 0.03, rel


class TestModelOnChip:
    def test_tiny_llama_quantized_decode(self):
        """End-to-end quantized prefill+decode executes on hardware."""
        from bigdl_tpu.llm.models.llama import (
            LlamaConfig, LlamaForCausalLM, quantize_params)
        import dataclasses
        cfg = dataclasses.replace(
            LlamaConfig.tiny(), hidden_size=256, intermediate_size=512,
            num_attention_heads=4, num_key_value_heads=2)
        model = LlamaForCausalLM.from_config(cfg, seed=0, max_cache_len=64)
        model.params = quantize_params(model.params)
        out = model.generate(np.array([[1, 2, 3]], np.int32),
                             max_new_tokens=4)
        assert out.shape == (1, 7)
        assert (np.asarray(out) < cfg.vocab_size).all()


class TestPagedAttentionOnChip:
    """The serving paged-KV kernel must lower via Mosaic and match the
    XLA gather reference ON HARDWARE at production shapes (VERDICT r3
    missing #1 — ragged paged attention for serving)."""

    @pytest.mark.parametrize("B,Hq,Hkv,maxp", [(4, 32, 32, 32),
                                               (8, 32, 8, 16)])
    def test_kernel_parity(self, B, Hq, Hkv, maxp):
        from bigdl_tpu.llm.kernels.paged_attention import (
            paged_attention_decode, paged_attention_reference)
        rs = np.random.RandomState(0)
        D, page, P = 128, 16, max(256, B * maxp + 1)
        q = jnp.asarray(rs.randn(B, Hq, D), jnp.bfloat16)
        kp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        vp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        bt = jnp.asarray(rs.permutation(P)[:B * maxp].reshape(B, maxp),
                         jnp.int32)
        lens = jnp.asarray(rs.randint(1, maxp * page, (B,)), jnp.int32)
        ker = np.asarray(paged_attention_decode(
            q, kp, vp, bt, lens, page_size=page), np.float32)
        ref = np.asarray(paged_attention_reference(
            q, kp, vp, bt, lens), np.float32)
        assert np.abs(ker - ref).max() < 0.05

    @pytest.mark.parametrize("B,Hq,Hkv,maxp", [(4, 32, 32, 32),
                                               (8, 32, 8, 16)])
    def test_stats_kernel_merge_parity(self, B, Hq, Hkv, maxp):
        """Round-5 serving decode structure on HARDWARE: stats kernel +
        self-token merge == write-then-attend reference at production
        shapes (what paged_decode_step runs inside its layer scan)."""
        from bigdl_tpu.llm.kernels.paged_attention import (
            merge_attention_partial, paged_attention_reference,
            paged_attention_stats)
        rs = np.random.RandomState(1)
        D, page, P = 128, 16, max(256, B * maxp + 1)
        q = jnp.asarray(rs.randn(B, Hq, D), jnp.bfloat16)
        kp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        vp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        bt = jnp.asarray(rs.permutation(P)[:B * maxp].reshape(B, maxp),
                         jnp.int32)
        lens = np.asarray(rs.randint(1, maxp * page - 1, (B,)), np.int32)
        k_new = jnp.asarray(rs.randn(B, Hkv, D) * 0.5, jnp.bfloat16)
        v_new = jnp.asarray(rs.randn(B, Hkv, D) * 0.5, jnp.bfloat16)
        acc, m, l = paged_attention_stats(q, kp, vp, bt,
                                          jnp.asarray(lens),
                                          page_size=page)
        got = np.asarray(merge_attention_partial(
            acc, m, l, q, k_new, v_new), np.float32)
        kp2, vp2 = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
        for bi in range(B):
            pid = int(bt[bi, lens[bi] // page])
            kp2[pid, :, lens[bi] % page] = np.asarray(k_new, np.float32)[bi]
            vp2[pid, :, lens[bi] % page] = np.asarray(v_new, np.float32)[bi]
        want = np.asarray(paged_attention_reference(
            q.astype(jnp.float32), jnp.asarray(kp2), jnp.asarray(vp2),
            bt, jnp.asarray(lens + 1)), np.float32)
        assert np.abs(got - want).max() < 0.05

    @pytest.mark.parametrize("B,Hq,Hkv,Tq", [(4, 32, 32, 64),
                                             (8, 32, 8, 32)])
    def test_ragged_prefill_kernel_parity(self, B, Hq, Hkv, Tq):
        """ISSUE 8: the ragged paged-PREFILL kernel must lower via
        Mosaic and match the XLA twin ON HARDWARE at production shapes
        — ragged prefix offsets (page-boundary, mid-page, zero) and
        ragged suffix lengths in one dispatch."""
        from bigdl_tpu.llm.kernels.ragged_prefill import (
            ragged_prefill_attention, ragged_prefill_reference)
        rs = np.random.RandomState(2)
        D, page, maxp = 128, 16, 16
        P = max(256, B * maxp + 1)
        q = jnp.asarray(rs.randn(B, Tq, Hq, D), jnp.bfloat16)
        ks = jnp.asarray(rs.randn(B, Tq, Hkv, D) * 0.5, jnp.bfloat16)
        vs = jnp.asarray(rs.randn(B, Tq, Hkv, D) * 0.5, jnp.bfloat16)
        kp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        vp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        bt = jnp.asarray(rs.permutation(P)[:B * maxp].reshape(B, maxp),
                         jnp.int32)
        offs = rs.randint(0, maxp * page, B).astype(np.int32)
        offs[0], offs[1 % B] = 0, page * 3          # full-prefill + boundary
        lens = rs.randint(1, Tq + 1, B).astype(np.int32)
        ker = np.asarray(ragged_prefill_attention(
            q, ks, vs, kp, vp, bt, jnp.asarray(offs),
            jnp.asarray(lens), page_size=page), np.float32)
        ref = np.asarray(ragged_prefill_reference(
            q, ks, vs, kp, vp, bt, jnp.asarray(offs),
            jnp.asarray(lens)), np.float32)
        for bi in range(B):
            sl = int(lens[bi])
            assert np.abs(ker[bi, :sl] - ref[bi, :sl]).max() < 0.05

    @staticmethod
    def _ragged_parity(seed, Hq, Hkv, Tq, maxp, offs, lens, window=None):
        """Kernel vs XLA twin over each row's true suffix length, bf16
        operands, head_dim 128, 16-token pages, a shuffled block table
        (page 0 left out, as in the engine)."""
        from bigdl_tpu.llm.kernels.ragged_prefill import (
            ragged_prefill_attention, ragged_prefill_reference)
        rs = np.random.RandomState(seed)
        B, D, page = len(offs), 128, 16
        P = B * maxp + 1
        q = jnp.asarray(rs.randn(B, Tq, Hq, D), jnp.bfloat16)
        ks = jnp.asarray(rs.randn(B, Tq, Hkv, D) * 0.5, jnp.bfloat16)
        vs = jnp.asarray(rs.randn(B, Tq, Hkv, D) * 0.5, jnp.bfloat16)
        kp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        vp = jnp.asarray(rs.randn(P, Hkv, page, D) * 0.5, jnp.bfloat16)
        bt = jnp.asarray(1 + rs.permutation(P - 1)[:B * maxp]
                         .reshape(B, maxp), jnp.int32)
        args = (q, ks, vs, kp, vp, bt, jnp.asarray(offs, jnp.int32),
                jnp.asarray(lens, jnp.int32))
        ker = np.asarray(ragged_prefill_attention(
            *args, page_size=page, sliding_window=window), np.float32)
        ref = np.asarray(ragged_prefill_reference(
            *args, sliding_window=window), np.float32)
        assert np.isfinite(ker).all()
        for bi, sl in enumerate(lens):
            assert np.abs(ker[bi, :sl] - ref[bi, :sl]).max() < 0.05

    @pytest.mark.parametrize("Hq,Hkv", [(32, 32), (32, 8)])
    @pytest.mark.parametrize("Tq,offs", [(128, (1900, 777)),
                                         (2048, (2000,)), (2048, (0,))])
    def test_ragged_prefill_served_tiles(self, Hq, Hkv, Tq, offs):
        """The tiles the engine dispatches at 7B widths: 128-token
        query tiles (hkv * qt * g = 4096 accumulator rows) for MHA-32
        and GQA-8, at one tile (Tq 128) and at the largest prefill
        bucket (Tq 2048, 16 query tiles), over long prefixes read in
        place — the VMEM limit the kernel states must hold here."""
        self._ragged_parity(3, Hq, Hkv, Tq, 128, offs,
                            [Tq - 3 * i for i in range(len(offs))])

    def test_ragged_prefill_sliding_window(self):
        """mistral_7b serves with sliding_window=4096: the windowed
        mask must lower too (a short window here, so it binds)."""
        self._ragged_parity(4, 32, 8, 256, 64, (700, 40), (256, 200),
                            window=300)


def _tiny_serving_model():
    """Shared tiny-Llama serving fixture: (model, prompt ids, greedy
    baseline) — one definition so every on-chip serving test pins the
    SAME shape and baseline."""
    import dataclasses
    from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), hidden_size=256, intermediate_size=512,
        num_attention_heads=4, num_key_value_heads=2)
    model = LlamaForCausalLM.from_config(cfg, seed=0, max_cache_len=64)
    ids = np.array([3, 1, 4, 1, 5], np.int32)
    want = model.generate(ids[None], max_new_tokens=6)[0, 5:]
    return model, ids, want


class TestPagedServingOnChip:
    def test_paged_server_greedy_parity_on_chip(self):
        """A paged LLMServer on hardware reproduces generate() exactly."""
        from bigdl_tpu.llm.serving import LLMServer
        model, ids, want = _tiny_serving_model()
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        try:
            got = srv.submit(ids, max_new_tokens=6).get(timeout=300)
        finally:
            srv.stop()
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_paged_server_parity_under_concurrent_load_on_chip(self):
        """The r4 buffer-lifetime race scenario ON HARDWARE with the r5
        scanned decode: 4 hammer threads of real device traffic while
        fresh servers serve greedy requests — every result must match
        generate() (r4's CPU repro was 14/30 mismatches pre-barrier;
        this pins 0/N on the real runtime too)."""
        import threading
        import time
        from bigdl_tpu.llm.serving import LLMServer
        model, ids, want = _tiny_serving_model()
        stop = threading.Event()

        def hammer():
            a = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
            f = jax.jit(lambda x: jnp.tanh(x @ x) + 1e-6)
            while not stop.is_set():
                a = f(a).block_until_ready()

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for it in range(6):
                srv = LLMServer(model, max_batch=2,
                                max_seq_len=32).start()
                try:
                    time.sleep((it % 4) * 0.001)
                    got = np.asarray(
                        srv.submit(ids, max_new_tokens=6).get(300))
                finally:
                    srv.stop()
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"iteration {it}")
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)


class TestPoolWrittenInPlaceOnChip:
    """ISSUE 26: the decode step and a prefill bucket, compiled at
    Mistral-7B widths and depth over a pool of the served size, make
    no array of the pool's shape by ``copy`` or ``transpose`` —
    the vectorised scatter they used before cost two such copies a pool
    in every step — and the in-place writers put the same bits in the
    same places as that scatter. ISSUE 28: the same two programs slice
    no layer out of a quantised weight stack (the scan used to, one
    copy of the layer's packed weights a linear) and hold the seven
    Mosaic calls they held: qkv, o, gate_up, down in two K chunks, the
    head, attention. All 32 layers, which cost the rolled scan no
    compile time: two layers deep a whole stack is small enough that
    XLA prefetches it into VMEM ahead of the kernel, layer by layer,
    and those ``slice`` s are no part of the served program."""

    LAYERS, PAGES, PAGE, BATCH, MAXP = 32, 2049, 16, 16, 128
    MOSAIC_CALLS = 7

    def _operands(self):
        import dataclasses
        from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                                synthetic_q4_params)
        cfg = dataclasses.replace(LlamaConfig.mistral_7b(),
                                  num_hidden_layers=self.LAYERS)
        params = jax.eval_shape(lambda: synthetic_q4_params(cfg, seed=0))
        shape = (self.LAYERS, self.PAGES, cfg.num_key_value_heads,
                 self.PAGE, cfg.hidden_size // cfg.num_attention_heads)
        return cfg, params, shape

    def _no_pool_copy(self, compiled, shape, layers):
        from bigdl_tpu.llm.kvcache.write import (pool_shaped_copies,
                                                 weight_slices)
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == self.MOSAIC_CALLS
        copies = pool_shaped_copies(text, shape)
        assert not copies, copies[0][:300]
        slices = weight_slices(text, layers)
        assert not slices, slices[0][:300]

    def test_decode_step_holds_no_pool_copy(self):
        import functools
        from bigdl_tpu.llm.models.llama import paged_decode_step_sampled
        cfg, params, shape = self._operands()
        pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        B = self.BATCH
        fn = jax.jit(functools.partial(paged_decode_step_sampled,
                                       page=self.PAGE),
                     static_argnums=1, donate_argnums=(2, 3))
        compiled = fn.lower(
            params, cfg, pool, pool,
            jax.ShapeDtypeStruct((B, self.MAXP), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.random.PRNGKey(0)).compile()
        self._no_pool_copy(compiled, shape, params["layers"])

    def test_prefill_bucket_holds_no_pool_copy(self):
        import functools
        from bigdl_tpu.llm.models.llama import paged_prefill_ragged
        cfg, params, shape = self._operands()
        pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        bucket = 256
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(functools.partial(paged_prefill_ragged,
                                       page=self.PAGE),
                     static_argnums=1, donate_argnums=(2, 3))
        compiled = fn.lower(
            params, cfg, pool, pool,
            jax.ShapeDtypeStruct((1, bucket), jnp.int32), i32, i32,
            jax.ShapeDtypeStruct((self.MAXP,), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.int32), i32, i32).compile()
        self._no_pool_copy(compiled, shape, params["layers"])

    @pytest.mark.parametrize("T,off", [(16, None), (256, 19), (3, 31)])
    def test_writers_match_the_scatter_on_chip(self, T, off):
        """Bit parity with ``.at[:, phys, :, slots].set`` on the chip's
        own tiled layout: decode rows (``off`` None) and runs that
        enter and leave a page mid-way."""
        from bigdl_tpu.llm.kvcache.write import write_kv, write_kv_run
        L, P, H, page, D = 2, 64, 8, self.PAGE, 128
        rs = np.random.RandomState(T)
        pool = jnp.asarray(rs.randn(L, P, H, page, D), jnp.bfloat16)
        new = jnp.asarray(rs.randn(L, T, H, D), jnp.float32)
        if off is None:
            phys = rs.permutation(np.arange(1, P))[:T].astype(np.int32)
            slots = rs.randint(0, page, T).astype(np.int32)
            phys[[0, 5]], slots[[0, 5]] = 0, 0
            writer = write_kv
        else:
            pos = off + np.arange(T)
            real = pos < off + T - 2            # two padded positions
            phys = np.where(real, 1 + pos // page, 0).astype(np.int32)
            slots = (pos % page).astype(np.int32)
            writer = write_kv_run
        want = np.asarray(pool.at[:, phys, :, slots].set(
            new.transpose(1, 0, 2, 3).astype(pool.dtype)), np.float32)
        got = np.asarray(jax.jit(writer)(pool, jnp.asarray(phys),
                                         jnp.asarray(slots), new),
                         np.float32)
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])


class TestStackedInt4OnChip:
    """ISSUE 28: the stacked form of ``int4_matmul`` (the layer a
    scalar-prefetch operand, the stack blocked in place) against the
    2-D form on ``q[l], scale[l]``, at Mistral-7B's four linear shapes,
    a decode batch (``corr``) and a prefill bucket (``sub8``): the same
    bits, under the real Mosaic lowering."""

    @pytest.mark.parametrize("m", [16, 512])
    @pytest.mark.parametrize("n,k", [(6144, 4096), (4096, 4096),
                                     (28672, 4096), (4096, 14336)])
    def test_stacked_equals_2d(self, n, k, m):
        L, layer = 3, 1
        tds = [_rand_quant(n, k, "sym_int4", seed=s)[2] for s in range(L)]
        q = jnp.asarray(np.stack([t["q"] for t in tds]))
        scale = jnp.asarray(np.stack([t["scale"] for t in tds]))
        x = jnp.asarray(np.random.RandomState(m).randn(m, k), jnp.bfloat16)
        got = jax.jit(lambda x, q, s, l: int4_matmul(
            x, q, s, layer=l, out_dtype=jnp.float32))(
                x, q, scale, jnp.int32(layer))
        want = int4_matmul(x, q[layer], scale[layer],
                           out_dtype=jnp.float32)
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        other = int4_matmul(x, q[0], scale[0], out_dtype=jnp.float32)
        assert not np.array_equal(np.asarray(got), np.asarray(other))


def _pr29_int4_twin(x, q, scale, sub8):
    """The arithmetic of the INT4 kernel's body as PR 29 had it, in
    plain ``jnp``: the scale rounded to bf16 (as the expansion matmul
    took it), every weight ``bf16(bf16(q) * bf16(s))``, the -8 either
    subtracted before the product (``sub8``) or folded through
    ``bf16(xe + xo) @ s_exp`` (``corr``), float32 accumulation."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    qi = q.astype(jnp.int32)
    s_exp = jnp.repeat(scale.astype(bf16), 16, axis=0)
    xe, xo = x[:, 0::2], x[:, 1::2]
    dot = functools.partial(jnp.dot, preferred_element_type=f32)
    lo, hi = qi & 0xF, qi >> 4
    if sub8:
        return (dot(xe, (lo - 8).astype(bf16) * s_exp)
                + dot(xo, (hi - 8).astype(bf16) * s_exp))
    return (dot(xe, lo.astype(bf16) * s_exp)
            + dot(xo, hi.astype(bf16) * s_exp)
            - 8.0 * dot(xe + xo, s_exp))


class TestInt4BodyOnChip:
    """ISSUE 30: the body that walks K in slabs, takes the scale in
    float32 as a sublane broadcast and rounds ``q * scale`` once,
    against PR 29's arithmetic (``_pr29_int4_twin``) and the float32
    product, at Mistral-7B's four linear shapes, a decode batch
    (``corr``) and a prefill bucket (``sub8``). The scale is no longer
    rounded to bf16, so the two differ in the last bits by design: the
    new body must sit as close to the float32 product as the old
    arithmetic did, and near the old arithmetic itself."""

    @pytest.mark.parametrize("m", [16, 512])
    @pytest.mark.parametrize("n,k", [(6144, 4096), (4096, 4096),
                                     (28672, 4096), (4096, 14336)])
    def test_not_further_from_float32_than_pr29(self, n, k, m):
        _, qd, td = _rand_quant(n, k, "sym_int4", seed=n + k)
        q, scale = jnp.asarray(td["q"]), jnp.asarray(td["scale"])
        x = jnp.asarray(np.random.RandomState(m).randn(m, k), jnp.bfloat16)
        got = int4_matmul(x, q, scale, out_dtype=jnp.float32)
        twin = jax.jit(_pr29_int4_twin, static_argnums=3)(
            x, q, scale, m >= 256)
        w = jnp.asarray(dequantize(qd).T)
        ref = jnp.dot(x.astype(jnp.float32), w, precision="highest")
        top = float(jnp.abs(ref).max())

        def errs(y):
            d = y - ref
            return (float(jnp.abs(d).max()) / top,
                    float(jnp.sqrt(jnp.mean(d * d))) / top)
        new, old = errs(got), errs(twin)
        apart = float(jnp.abs(got - twin).max()) / top
        print(f"\nint4 body n={n} k={k} m={m}: max/rms error against "
              f"float32 new {new[0]:.5f}/{new[1]:.6f}, PR 29 twin "
              f"{old[0]:.5f}/{old[1]:.6f}; new against twin {apart:.5f}")
        assert new[1] <= old[1], (new, old)
        # the maximum over 1e5-1e7 outputs is an extreme value: 5 % of room
        assert new[0] <= 1.05 * old[0], (new, old)
        assert apart < 0.02


class TestInt4SplitOnChip:
    """ISSUE 38: the kernel's even/odd planes and group sums, built in
    VMEM by the selection product on the MXU (bf16 operands, float32
    accumulation), against ``x[:, 0::2]``, ``x[:, 1::2]`` bit for bit
    and the float32 group sums, at the served chunks: K = 4,096 and
    ``down_proj``'s 7,168, a decode tile and a prefill tile."""

    @pytest.mark.parametrize("m", [16, 128])
    @pytest.mark.parametrize("kc", [4096, 7168, 5504])
    def test_planes_and_sums(self, kc, m):
        import importlib
        from jax.experimental import pallas as pl
        im = importlib.import_module("bigdl_tpu.llm.kernels.int4_matmul")

        def kern(x_ref, xe_ref, xo_ref, xs_ref, *stack):
            im._deinterleave(x_ref, xe_ref, xo_ref, xs_ref, *stack,
                             cdt=jnp.bfloat16)

        x = jnp.asarray(np.random.RandomState(kc + m).randn(m, kc),
                        jnp.bfloat16)
        plane = jax.ShapeDtypeStruct((m, kc // 2), jnp.bfloat16)
        xe, xo, xs = pl.pallas_call(
            kern, grid=(1,),
            in_specs=[pl.BlockSpec((m, kc), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((m, kc // 2), lambda i: (0, 0))] * 2
            + [pl.BlockSpec((m, kc // 32), lambda i: (0, 0))],
            out_shape=[plane, plane,
                       jax.ShapeDtypeStruct((m, kc // 32), jnp.float32)],
            scratch_shapes=im._split_scratch(m, kc, sub8=False)[3:])(x)
        np.testing.assert_array_equal(
            np.asarray(xe).view(np.uint16),
            np.asarray(x[:, 0::2]).view(np.uint16))
        np.testing.assert_array_equal(
            np.asarray(xo).view(np.uint16),
            np.asarray(x[:, 1::2]).view(np.uint16))
        want = np.asarray(x, np.float32).reshape(m, -1, 32).sum(-1)
        np.testing.assert_allclose(np.asarray(xs), want, rtol=1e-6,
                                   atol=1e-5)


class TestLatentFamilyOnChip:
    """ISSUE 27: the deepseek_v3 family at the published widths of
    Kanana-2-30B-A3B, 8 layers, over the pool the benchmark's engine
    holds (``P`` = 1 + 32 x 512 pages of 16): the decode program and
    three prefill buckets compile, hold their Mosaic calls and make no
    copy shaped like the latent pool; the latent kernel and the expert
    product agree with their ``jnp`` twins on the chip's own layout."""

    PAGES, PAGE, BATCH, MAXP = 1 + 32 * 512, 16, 32, 512

    def _operands(self):
        from bigdl_tpu.llm.models import deepseek
        cfg = deepseek.DeepseekConfig(num_hidden_layers=8)
        params = jax.eval_shape(lambda: deepseek.init_params(cfg, 0))
        pool = jax.eval_shape(lambda: deepseek.page_classes(cfg)[0].pools(
            self.PAGES, self.PAGE, jnp.bfloat16)[0])
        assert pool.shape == (8, self.PAGES, 1, 16, 640)
        return deepseek, cfg, params, pool

    def _holds_kernels_and_no_pool_copy(self, compiled, pool, calls):
        from bigdl_tpu.llm.kvcache.write import pool_shaped_copies
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= calls
        copies = pool_shaped_copies(text, pool.shape)
        assert not copies, copies[0][:300]

    def test_decode_program(self):
        import functools
        deepseek, cfg, params, pool = self._operands()
        B = self.BATCH
        fn = jax.jit(functools.partial(deepseek.paged_decode_step_sampled,
                                       page=self.PAGE),
                     static_argnums=(1, 3), donate_argnums=(2,))
        compiled = fn.lower(
            params, cfg, pool, None,
            jax.ShapeDtypeStruct((B, self.MAXP), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.random.PRNGKey(0)).compile()
        # the latent kernel in layer 0 and in the scan, the expert
        # product in the scan
        self._holds_kernels_and_no_pool_copy(compiled, pool, 3)

    @pytest.mark.parametrize("bucket", [128, 1024, 4096])
    def test_prefill_program(self, bucket):
        import functools
        deepseek, cfg, params, pool = self._operands()
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(functools.partial(deepseek.paged_prefill_ragged,
                                       page=self.PAGE),
                     static_argnums=(1, 3), donate_argnums=(2,))
        compiled = fn.lower(
            params, cfg, pool, None,
            jax.ShapeDtypeStruct((1, bucket), jnp.int32), i32, i32,
            jax.ShapeDtypeStruct((self.MAXP,), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.int32), i32, i32).compile()
        self._holds_kernels_and_no_pool_copy(compiled, pool, 1)

    @staticmethod
    def _cell_lengths():
        """32 rows as the cell's engine holds them: seven with nothing
        cached, scattered, the others up to the table's last token,
        block edges among them."""
        rs = np.random.RandomState(32)
        lens = rs.randint(1, 8192, 32)
        lens[[0, 5, 6, 17, 30, 31, 12]] = 0
        lens[[1, 2, 3, 4]] = [8191, 512, 513, 1024]
        return lens.tolist()

    @pytest.mark.parametrize("maxp,pages,lens", [
        (128, 300, [0, 1, 517, 500]), (128, 300, [0, 1, 517, 2000]),
        (512, 1 + 32 * 512, None)],
        ids=["one_block", "four_blocks", "cell"])
    def test_latent_kernel_matches_its_twin(self, maxp, pages, lens):
        """Contexts inside one block of ``LATENT_BLOCK_TOKENS`` (512)
        cached tokens, and over four; the cell's own shape: 32 rows,
        a table of 512 pages, lengths 0 to 8,191."""
        from bigdl_tpu.llm.kernels import paged_attention as pa
        rs = np.random.RandomState(0)
        lens = lens or self._cell_lengths()
        b, h, w, dv = len(lens), 32, 640, 512
        q = jnp.asarray(rs.randn(b, h, w), jnp.float32)
        pool = jnp.asarray(rs.randn(pages, 1, self.PAGE, w), jnp.bfloat16)
        bt = jnp.asarray(rs.randint(1, pages, (b, maxp)), jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        scale = 192 ** -0.5
        with jax.default_matmul_precision("highest"):
            want = pa.latent_attention_reference_stats(
                q, pool, bt, lens, dv=dv, scale=scale)
        got = pa.latent_attention_decode_stats(
            q, pool, bt, lens, page_size=self.PAGE, dv=dv, scale=scale)
        # the kernel's float32 products take fewer passes than XLA's
        # "highest": 1.4 % of the largest sum was seen
        for g, w_, tol in zip(got, want, (3e-2, 1e-3, 3e-2)):
            w_ = np.asarray(w_)
            np.testing.assert_allclose(np.asarray(g), w_, rtol=tol,
                                       atol=tol * np.abs(w_).max())

    @pytest.mark.parametrize("t", [32, 640])
    def test_expert_product_matches_its_twin(self, t):
        from bigdl_tpu.llm.kernels import moe
        rs = np.random.RandomState(t)
        k, g, hid, width, layers = 8, 130, 2048, 768, 2
        x = jnp.asarray(rs.randn(t, hid), jnp.bfloat16)
        groups = jnp.asarray(np.stack(
            [rs.permutation(g)[:k] for _ in range(t)]), jnp.int32)
        w = jnp.asarray(rs.rand(t, k), jnp.float32)
        live = jnp.asarray(rs.rand(t) > 0.1)
        key = jax.random.PRNGKey(t)
        wgu = (jax.random.normal(key, (layers * g, hid, 2 * width),
                                 jnp.float32) / 45).astype(jnp.bfloat16)
        wd = (jax.random.normal(key, (layers * g, width, hid),
                                jnp.float32) / 28).astype(jnp.bfloat16)
        got, sizes = jax.jit(lambda *a: moe.grouped_ffn(*a, 1, g))(
            x, groups, w, live, wgu, wd)
        # the same sums in plain XLA: every group over every token,
        # kept where the token was assigned to it
        table = np.zeros((t, g), np.float32)
        np.put_along_axis(table, np.asarray(groups), np.asarray(w), 1)
        table = jnp.asarray(table * np.asarray(live)[:, None])

        @jax.jit
        def twin(x, table, wgu, wd):
            def one(y, e):
                gu = x.astype(jnp.float32) @ wgu[g + e].astype(jnp.float32)
                act = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
                    .astype(jnp.bfloat16).astype(jnp.float32)
                return y + table[:, e, None] * (
                    act @ wd[g + e].astype(jnp.float32)), None
            with jax.default_matmul_precision("highest"):
                return jax.lax.scan(one, jnp.zeros((t, hid), jnp.float32),
                                    jnp.arange(g))[0]
        want = np.asarray(twin(x, table, wgu, wd))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2,
                                   atol=2e-2 * np.abs(want).max())
        assert int(sizes.sum()) == int(live.sum()) * k


class TestHybridFamilyOnChip:
    """ISSUE 31: the mimo_v2 family at the published widths of
    MiMo-V2.5's language model, layer 0 and one period (7 layers, 16 of
    256 experts held), over the two pools the benchmark's engine holds
    (full class 43,000 pages, window class 1 + 32 rings of 16): the
    decode program and three prefill buckets (one pass, and two that
    loop over chunks inside the program) compile, hold their Mosaic
    calls and make no copy shaped like either pool; the four attention
    kernels and the held experts' product agree with their ``jnp``
    twins on the chip's own layout."""

    PAGES, PAGE, BATCH, MAXP, RING = 43000, 16, 32, 2176, 16

    def _operands(self):
        from bigdl_tpu.llm.models import mimo
        cfg = mimo.MimoConfig(
            num_hidden_layers=7, hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
            moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), experts_held=16)
        params = jax.eval_shape(lambda: mimo.init_params(cfg, 0))
        full, window = mimo.page_classes(cfg)
        pools = (jax.eval_shape(lambda: full.pools(
                     self.PAGES, self.PAGE, jnp.bfloat16)[0]),
                 jax.eval_shape(lambda: window.pools(
                     1 + self.BATCH * self.RING, self.PAGE,
                     jnp.bfloat16)[0]))
        assert pools[0].shape == (2, self.PAGES, 4, 16, 384)
        assert pools[1].shape == (5, 513, 8, 16, 384)
        return mimo, cfg, params, pools

    def _holds_kernels_and_no_pool_copy(self, compiled, pools, calls):
        from bigdl_tpu.llm.kvcache.write import pool_shaped_copies
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= calls
        for pool in pools:
            flat = (pool.shape[0] * pool.shape[1],) + pool.shape[2:]
            copies = pool_shaped_copies(text, pool.shape) \
                + pool_shaped_copies(text, flat)
            assert not copies, copies[0][:300]

    def test_decode_program(self):
        import functools
        mimo, cfg, params, pools = self._operands()
        B = self.BATCH
        fn = jax.jit(functools.partial(mimo.paged_decode_step_sampled,
                                       page=self.PAGE),
                     static_argnums=(1,), donate_argnums=(2,))
        compiled = fn.lower(
            params, cfg, pools, (None, None),
            (jax.ShapeDtypeStruct((B, self.MAXP), jnp.int32),
             jax.ShapeDtypeStruct((B, self.RING), jnp.int32)),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.random.PRNGKey(0)).compile()
        # an attention kernel a layer (7), the expert product of the six
        # expert layers
        self._holds_kernels_and_no_pool_copy(compiled, pools, 13)

    @pytest.mark.parametrize("bucket", [256, 2048, 32768])
    def test_prefill_program(self, bucket):
        import functools
        mimo, cfg, params, pools = self._operands()
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        run = jax.ShapeDtypeStruct((bucket,), jnp.int32)
        fn = jax.jit(functools.partial(mimo.paged_prefill_ragged,
                                       page=self.PAGE),
                     static_argnums=(1,), donate_argnums=(2,))
        compiled = fn.lower(
            params, cfg, pools, (None, None),
            jax.ShapeDtypeStruct((1, bucket), jnp.int32), i32, i32,
            (jax.ShapeDtypeStruct((self.MAXP,), jnp.int32),
             jax.ShapeDtypeStruct((self.RING,), jnp.int32)),
            (run, run), run, i32, i32).compile()
        self._holds_kernels_and_no_pool_copy(compiled, pools, 13)

    @staticmethod
    def _pool(rs, pages, hkv):
        kv = np.zeros((pages, hkv, 16, 384), np.float32)
        kv[..., :192] = rs.randn(pages, hkv, 16, 192)
        kv[..., 256:] = rs.randn(pages, hkv, 16, 128)
        return jnp.asarray(kv, jnp.bfloat16)

    @pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
    def test_decode_kernel_matches_its_twin(self, window):
        """Rows of no cached token, inside one block of 512 cached
        tokens, over several blocks and (the ring) past several wraps."""
        from bigdl_tpu.llm.kernels import hybrid_attention as ha
        rs = np.random.RandomState(0)
        b, hq = 5, 64
        hkv, cols = (4, 640) if window is None else (8, self.RING)
        kv = self._pool(rs, 1 + b * cols, hkv)
        bt = jnp.asarray(1 + np.arange(b * cols).reshape(b, cols), jnp.int32)
        lens = jnp.asarray([0, 7, 500, 2049, 10000], jnp.int32)
        q = np.zeros((b, hq, 256), np.float32)
        q[..., :192] = rs.randn(b, hq, 192)
        q = jnp.asarray(q, jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            want = ha.attention_decode_reference_stats(
                q, kv, bt, lens, scale=192 ** -0.5, window=window)
        got = ha.attention_decode_stats(q, kv, bt, lens, page_size=16,
                                        scale=192 ** -0.5, window=window)
        # the softmax weights are rounded to bfloat16 in front of P V
        for g, w_, tol in zip(got, want, (1e-2, 1e-3, 1e-2)):
            w_ = np.asarray(w_)
            np.testing.assert_allclose(np.asarray(g), w_, rtol=tol,
                                       atol=tol * np.abs(w_).max())

    @pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
    def test_decode_kernel_at_the_cells_shape(self, window):
        """ISSUE 34: the walk at the shape the cell's decode program
        calls it with: 32 rows, a table of 2,176 columns (a ring of 16),
        14 live rows of 0.3k-34.8k tokens, dead rows before, between
        and after them, a row of whole blocks among them. The twin is
        asked a live row at a time (the gather of 32 whole tables in
        float32 would not fit)."""
        from bigdl_tpu.llm.kernels import hybrid_attention as ha
        rs = np.random.RandomState(2)
        lens = np.asarray(
            [0, 0, 15546, 0, 3621, 0, 6211, 0, 0, 0, 2774, 6391, 300, 0, 0,
             0, 0, 0, 0, 0, 0, 0, 10283, 0, 34815, 2064, 2166, 0, 512, 2712,
             903, 5141], np.int32)
        b, hq = self.BATCH, 64
        hkv, cols = (4, self.MAXP) if window is None else (8, self.RING)
        held = np.where(lens > 0, cols, 0) if window else -(-lens // 16)
        bt = np.zeros((b, cols), np.int32)      # past a row's pages: trash
        free = 1 + rs.permutation(int(held.sum()))
        for r, n in enumerate(held):
            bt[r, :n], free = free[:n], free[n:]
        kv = self._pool(rs, 1 + int(held.sum()), hkv)
        q = np.zeros((b, hq, 256), np.float32)
        q[..., :192] = rs.randn(b, hq, 192)
        q, bt = jnp.asarray(q, jnp.bfloat16), jnp.asarray(bt)
        got = ha.attention_decode_stats(q, kv, bt, jnp.asarray(lens),
                                        page_size=16, scale=192 ** -0.5,
                                        window=window)
        got = [np.asarray(g) for g in got]
        twin = jax.jit(functools.partial(
            ha.attention_decode_reference_stats, scale=192 ** -0.5,
            window=window))
        for r in range(b):
            if not lens[r]:
                for g, identity in zip(got, (0.0, -1e30, 0.0)):
                    assert (g[r] == np.float32(identity)).all(), r
                continue
            with jax.default_matmul_precision("highest"):
                want = twin(q[r:r + 1], kv, bt[r:r + 1],
                            jnp.asarray(lens[r:r + 1]))
            for g, w_, tol in zip(got, want, (1e-2, 1e-3, 1e-2)):
                w_ = np.asarray(w_)[0]
                np.testing.assert_allclose(
                    g[r], w_, rtol=tol, atol=tol * np.abs(w_).max(),
                    err_msg=f"row {r} of {lens[r]} tokens")

    @pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
    @pytest.mark.parametrize("off,slen", [(0, 1024), (3000, 777)])
    def test_prefill_kernel_matches_its_twin(self, window, off, slen):
        from bigdl_tpu.llm.kernels import hybrid_attention as ha
        rs = np.random.RandomState(1)
        hq, tq = 64, 1024
        hkv, cols = (4, 256) if window is None else (8, self.RING)
        kv = self._pool(rs, 1 + cols, hkv)
        bt = jnp.asarray(1 + np.arange(cols)[None], jnp.int32)

        def padded(shape, used, width):
            a = np.zeros(shape + (width,), np.float32)
            a[..., :used] = rs.randn(*shape, used)
            return jnp.asarray(a, jnp.bfloat16)
        q = padded((1, tq, hq), 192, 256)
        ks, vs = padded((1, tq, hkv), 192, 256), padded((1, tq, hkv), 128,
                                                        128)
        sink = None if window is None else \
            jnp.asarray(4 + rs.randn(hq), jnp.float32)
        args = (q, ks, vs, kv, bt, jnp.asarray([off], jnp.int32),
                jnp.asarray([slen], jnp.int32), sink)
        with jax.default_matmul_precision("highest"):
            want = ha.prefill_attention_reference(
                *args, scale=192 ** -0.5, window=window)
        got = ha.prefill_attention(*args, page_size=16, scale=192 ** -0.5,
                                   window=window)
        want = np.asarray(want, np.float32)[0, :slen]
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[0, :slen], want, rtol=2e-2,
            atol=2e-2 * np.abs(want).max())

    @pytest.mark.parametrize("t", [24, 1024])
    def test_held_experts_product_matches_its_twin(self, t):
        """16 of 256 experts held: decode tiles and prefill tiles."""
        from bigdl_tpu.llm.kernels import moe
        rs = np.random.RandomState(t)
        k, n, held, hid, width = 8, 256, 16, 4096, 2048
        x = jnp.asarray(rs.randn(t, hid), jnp.bfloat16)
        # half of every token's choices among the held, so that they work
        groups = np.stack([np.concatenate([
            rs.permutation(held)[:k // 2],
            held + rs.permutation(n - held)[:k // 2]]) for _ in range(t)])
        groups = jnp.asarray(groups, jnp.int32)
        w = jnp.asarray(rs.rand(t, k), jnp.float32)
        live = jnp.asarray(rs.rand(t) > 0.1)
        key = jax.random.PRNGKey(t)
        wgu = (jax.random.normal(key, (held, hid, 2 * width), jnp.float32)
               / 64).astype(jnp.bfloat16)
        wd = (jax.random.normal(key, (held, width, hid), jnp.float32)
              / 45).astype(jnp.bfloat16)
        got, sizes = jax.jit(lambda *a: moe.grouped_ffn(
            *a, 0, held, held=(0, held)))(x, groups, w, live, wgu, wd)
        table = np.zeros((t, n), np.float32)
        np.put_along_axis(table, np.asarray(groups), np.asarray(w), 1)
        table = jnp.asarray(table * np.asarray(live)[:, None])

        @jax.jit
        def twin(x, table, wgu, wd):
            def one(y, e):
                gu = x.astype(jnp.float32) @ wgu[e].astype(jnp.float32)
                act = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
                    .astype(jnp.bfloat16).astype(jnp.float32)
                return y + table[:, e, None] * (
                    act @ wd[e].astype(jnp.float32)), None
            with jax.default_matmul_precision("highest"):
                return jax.lax.scan(one, jnp.zeros((t, hid), jnp.float32),
                                    jnp.arange(held))[0]
        want = np.asarray(twin(x, table, wgu, wd))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2,
                                   atol=2e-2 * np.abs(want).max())
        assert int(sizes.sum()) == int(live.sum()) * k // 2


class TestRetentionFamilyOnChip:
    """ISSUE 33: the brumby family at Brumby-14B-Base's published
    widths, 8 of its 40 layers, over the state arrays the benchmark's
    engine holds (20 slots and the trash row, 5.77 GB): the decode
    program and two prefill buckets (one pass, and one that carries the
    state from chunk to chunk inside the program) compile, hold a
    Mosaic call a layer and make no copy shaped like the state; the two
    retention kernels agree with their ``jnp`` twins on the chip's own
    layout, the decode kernel leaves the rows of dead batch rows
    alone."""

    BATCH = 20

    def _operands(self):
        from bigdl_tpu.llm.models import brumby
        cfg = brumby.BrumbyConfig(num_hidden_layers=8)
        params = jax.eval_shape(lambda: brumby.init_params(cfg, 0))
        (state,) = brumby.page_classes(cfg)
        arrays = jax.eval_shape(lambda: state.arrays(self.BATCH))
        assert arrays[0].shape == (8, 21, 8, 128, 8320)
        assert arrays[1].shape == (8, 21, 8, 8320)
        return brumby, cfg, params, arrays

    def _holds_kernels_and_no_state_copy(self, compiled, arrays):
        from bigdl_tpu.llm.kvcache.write import pool_shaped_copies
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 8
        for a in arrays:            # the state and the normaliser
            flat = (a.shape[0] * a.shape[1],) + a.shape[2:]
            copies = pool_shaped_copies(text, a.shape) \
                + pool_shaped_copies(text, flat)
            assert not copies, copies[0][:300]

    def test_decode_program(self):
        import functools
        from bigdl_tpu.llm.kernels.sampling import make_sampled_step
        brumby, cfg, params, arrays = self._operands()
        B = self.BATCH
        fn = jax.jit(functools.partial(
            make_sampled_step(brumby.paged_decode_step), page=16),
            static_argnums=(1,), donate_argnums=(2, 3))
        compiled = fn.lower(
            params, cfg, *arrays, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.random.PRNGKey(0)).compile()
        self._holds_kernels_and_no_state_copy(compiled, arrays)

    @pytest.mark.parametrize("bucket", [256, 4096])
    def test_prefill_program(self, bucket):
        import functools
        brumby, cfg, params, arrays = self._operands()
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        fn = jax.jit(functools.partial(brumby.paged_prefill_ragged, page=16),
                     static_argnums=(1,), donate_argnums=(2, 3))
        compiled = fn.lower(
            params, cfg, *arrays, i32(1, bucket), i32(), i32(), i32(1),
            i32(bucket), i32(bucket), i32(), i32()).compile()
        self._holds_kernels_and_no_state_copy(compiled, arrays)

    def _inputs(self, b, rows):
        from bigdl_tpu.llm.kernels import retention
        ks = iter(jax.random.split(jax.random.PRNGKey(11), 8))
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True))
        p = retention.state_width(128)
        return (jax.random.normal(next(ks), (rows, 8, 128, p)),
                30 + jnp.abs(jax.random.normal(next(ks), (rows, 8, p))),
                unit(jax.random.normal(next(ks), (b, 8, 5, 128))
                     ).astype(jnp.bfloat16),
                unit(jax.random.normal(next(ks), (b, 8, 128))
                     ).astype(jnp.bfloat16),
                jax.random.normal(next(ks), (b, 8, 128)
                                  ).astype(jnp.bfloat16),
                jax.nn.log_sigmoid(4 + jax.random.normal(next(ks), (b, 8))))

    @staticmethod
    def _rel(got, want):
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        return float(np.sqrt(((got - want) ** 2).mean()
                             / (want ** 2).mean()))

    def test_decode_kernel_matches_its_twin(self):
        """At the cell's shapes: 20 batch rows of which 11 are live
        (dead rows between them, a live row last), 5 query heads a KV
        head. The kernel updates the state AND the normaliser."""
        from bigdl_tpu.llm.kernels import retention
        state, z, q, k, v, g = self._inputs(20, 21)
        live = jnp.asarray([i % 2 == 1 or i == 0 for i in range(20)])
        assert int(live.sum()) == 11 and bool(live[-1])
        slots = jnp.where(live, 1 + jnp.arange(20), 0).astype(jnp.int32)
        want = jax.jit(lambda *a: retention._decode_xla(*a, 1e-6))(
            state, z, q, k, v, g, slots, live)
        got = jax.jit(retention.retention_decode)(
            state, z, q, k, v, g, slots, live)
        lv = np.asarray(live)
        held = np.asarray(slots)[lv]
        # the read-out is a bfloat16 product, the update float32
        assert self._rel(np.asarray(got[0])[lv],
                         np.asarray(want[0])[lv]) < 0.006
        assert self._rel(np.asarray(got[1])[held],
                         np.asarray(want[1])[held]) < 1e-5
        assert self._rel(np.asarray(got[2])[held],
                         np.asarray(want[2])[held]) < 1e-5
        # the slots of the nine dead batch rows, both arrays
        for row in 1 + np.flatnonzero(~lv):
            assert float(jnp.abs(got[1][row] - state[row]).max()) == 0
            assert float(jnp.abs(got[2][row] - z[row]).max()) == 0

    @pytest.mark.parametrize("fresh,n_live", [(True, 1024), (False, 700)])
    def test_prefill_kernel_matches_its_twin(self, fresh, n_live):
        from bigdl_tpu.llm.kernels import retention
        state, z, q, k, v, g = self._inputs(1024, 4)

        def twin(st, zz):
            qt, kt, vt, end = retention._fold_gates(q, k, v, g, n_live, 256)
            with jax.default_matmul_precision("highest"):
                y, s, zn = retention._chunk_xla(
                    jnp.where(fresh, 0, st[2]), jnp.where(fresh, 0, zz[2]),
                    qt, kt, vt, end, 128, 1e-6, 256)
            return y.transpose(2, 0, 1, 3), s, zn
        want = jax.jit(twin)(state, z)
        got = jax.jit(lambda st, zz: retention.retention_prefill_chunk(
            st, zz, q, k, v, g, jnp.int32(2), fresh, jnp.int32(n_live)))(
            state, z)
        assert self._rel(np.asarray(got[0])[:n_live],
                         np.asarray(want[0])[:n_live]) < 0.008
        assert self._rel(got[1][2], want[1]) < 0.004
        assert self._rel(got[2][2], want[2]) < 1e-5
        for row in (0, 1, 3):
            assert float(jnp.abs(got[1][row] - state[row]).max()) == 0


class TestStateSpaceFamilyOnChip:
    """ISSUE 37: the nemotron_h family at Nemotron-3-Super's published
    widths, the first pipeline stage's 11 layers and a quarter of every
    expert layer, over what the benchmark's engine holds (64 slots and
    the trash row of state, 49,153 pages): the decode program and two
    prefill buckets (one pass, and one that carries state, window and
    pages from chunk to chunk inside the program) compile, hold a Mosaic
    call a Mamba-2, attention and expert layer and make no copy shaped
    like the state or the pools; the two state-space kernels and the
    relu2 expert product agree with their ``jnp`` twins on the chip's
    own layout, and the decode kernel leaves the rows of dead batch rows
    alone."""

    BATCH = 64
    PAGES = 49153

    def _operands(self):
        from bigdl_tpu.llm.models import nemotron_h as nh
        cfg = nh.NemotronHConfig(
            num_hidden_layers=11, hybrid_override_pattern="MEMEMEM*EME",
            experts_held=128)
        params = jax.eval_shape(lambda: nh.init_params(cfg, 0))
        kv, state = nh.page_classes(cfg)
        k, v = jax.eval_shape(lambda: kv.pools(self.PAGES, 16,
                                               jnp.bfloat16))
        s, w = jax.eval_shape(lambda: state.arrays(self.BATCH))
        assert s.shape == (5, 65, 128, 64, 128) and s.dtype == jnp.float32
        assert w.shape == (5, 65, 3, 10240) and w.dtype == jnp.bfloat16
        return nh, cfg, params, ((k, s), (v, w))

    def _holds_kernels_and_no_copy(self, compiled, arrays):
        from bigdl_tpu.llm.kvcache.write import pool_shaped_copies
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 11
        for a in (arrays[0][0], arrays[1][0], arrays[0][1]):
            flat = (a.shape[0] * a.shape[1],) + a.shape[2:]
            copies = pool_shaped_copies(text, a.shape) \
                + pool_shaped_copies(text, flat)
            assert not copies, copies[0][:300]

    def test_decode_program(self):
        import functools
        nh, cfg, params, (pools, others) = self._operands()
        B = self.BATCH
        fn = jax.jit(functools.partial(nh.paged_decode_step_sampled,
                                       page=16),
                     static_argnums=(1,), donate_argnums=(2, 3))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        compiled = fn.lower(
            params, cfg, pools, others, (i32(B, 1024), i32(B, 1)), i32(B),
            jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.random.PRNGKey(0)).compile()
        self._holds_kernels_and_no_copy(compiled, (pools, others))

    @pytest.mark.parametrize("bucket", [256, 4096])
    def test_prefill_program(self, bucket):
        import functools
        nh, cfg, params, (pools, others) = self._operands()
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        fn = jax.jit(functools.partial(nh.paged_prefill_ragged, page=16),
                     static_argnums=(1,), donate_argnums=(2, 3))
        compiled = fn.lower(
            params, cfg, pools, others, i32(1, bucket), i32(), i32(),
            (i32(1024), i32(1)), (i32(bucket), i32(bucket)), i32(bucket),
            i32(), i32()).compile()
        self._holds_kernels_and_no_copy(compiled, (pools, others))

    @staticmethod
    def _inputs(b, rows, seed=37):
        ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
        return dict(
            state=jax.random.normal(next(ks), (rows, 128, 64, 128)),
            x=jax.random.normal(next(ks), (b, 128, 64)),
            bm=jax.random.normal(next(ks), (b, 8, 128)),
            cm=jax.random.normal(next(ks), (b, 8, 128)),
            dt=jax.nn.softplus(jax.random.normal(next(ks), (b, 128)) - 4),
            a=-jax.random.uniform(next(ks), (128,), minval=1.0,
                                  maxval=16.0),
            d=1 + 0.5 * jax.random.normal(next(ks), (128,)))

    _rel = staticmethod(TestRetentionFamilyOnChip._rel)

    def test_decode_kernel_matches_its_twin(self):
        """At the cell's shapes: 64 batch rows of which 33 are live
        (dead rows between them, a live row last)."""
        from bigdl_tpu.llm.kernels import ssm
        a = self._inputs(64, 65)
        live = jnp.asarray([i % 2 == 1 or i == 0 for i in range(64)])
        assert int(live.sum()) == 33 and bool(live[-1])
        slots = jnp.where(live, 1 + jnp.arange(64), 0).astype(jnp.int32)
        args = (a["state"], a["x"], a["bm"], a["cm"], a["dt"], a["a"],
                a["d"], slots, live)

        def twin(state, x, bm, cm, dt, av, d, slots, live):
            with jax.default_matmul_precision("highest"):
                y, s = ssm._decode_xla(state, dt[..., None] * x, bm, cm,
                                       jnp.exp(dt * av), slots)
            return y + d[None, :, None] * x, s
        want = jax.jit(twin)(*args)
        got = jax.jit(ssm.ssm_decode)(*args)
        lv = np.asarray(live)
        held = np.asarray(slots)[lv]
        # the read-out is a bfloat16 product, the update float32
        assert self._rel(np.asarray(got[0])[lv],
                         np.asarray(want[0])[lv]) < 0.006
        assert float(jnp.abs(got[0][~live]).max()) == 0
        assert self._rel(np.asarray(got[1])[held],
                         np.asarray(want[1])[held]) < 1e-5
        for row in 1 + np.flatnonzero(~lv):
            assert float(jnp.abs(got[1][row] - a["state"][row]).max()) == 0

    @pytest.mark.parametrize("fresh,n_live", [(True, 1024), (False, 700)])
    def test_prefill_kernel_matches_its_twin(self, fresh, n_live):
        from bigdl_tpu.llm.kernels import ssm
        a = self._inputs(1024, 4)
        bm, cm = a["bm"] / 8, a["cm"] / 8

        def twin(state):
            with jax.default_matmul_precision("highest"):
                dt, cum = ssm._running_sums(a["dt"], a["a"], n_live, 128)
                y, s = ssm._chunk_xla(jnp.where(fresh, 0, state[2]),
                                      dt[..., None] * a["x"], bm, cm, cum,
                                      128)
            return y + a["d"][None, :, None] * a["x"], s
        want = jax.jit(twin)(a["state"])
        got = jax.jit(lambda st: ssm.ssd_prefill_chunk(
            st, a["x"], bm, cm, a["dt"], a["a"], a["d"], jnp.int32(2),
            fresh, jnp.int32(n_live)))(a["state"])
        assert self._rel(np.asarray(got[0])[:n_live],
                         np.asarray(want[0])[:n_live]) < 0.008
        assert self._rel(got[1][2], want[1]) < 0.004
        for row in (0, 1, 3):
            assert float(jnp.abs(got[1][row] - a["state"][row]).max()) == 0

    @pytest.mark.parametrize("t", [64, 1024])
    def test_latent_experts_product_matches_its_twin(self, t):
        """22 of 512 a token, 128 held, relu2 in the 1,024-wide latent:
        a decode batch (tiles of 16) and a prefill chunk (of 128)."""
        from bigdl_tpu.llm.kernels import moe
        ks = jax.random.split(jax.random.PRNGKey(t), 5)
        x = jax.random.normal(ks[0], (t, 1024)).astype(jnp.bfloat16)
        idx = jnp.argsort(jax.random.uniform(ks[1], (t, 512)))[:, :22] \
            .astype(jnp.int32)
        w = jax.random.uniform(ks[2], (t, 22))
        up = (jax.random.normal(ks[3], (128, 1024, 2688)) / 32
              ).astype(jnp.bfloat16)
        down = (jax.random.normal(ks[4], (128, 2688, 1024)) / 52
                ).astype(jnp.bfloat16)
        live = jnp.ones(t, bool)
        run = lambda interpret: jax.jit(
            lambda *a: moe.grouped_ffn(
                *a, 0, 128, held=(0, 128), activation="relu2",
                interpret=interpret))(x, idx, w, live, up, down)
        got, sizes = run(False)
        tm = moe.tile_rows(t)
        d = moe.dispatch(jnp.where(idx < 128, idx, 0), idx < 128, 128, tm)
        x_pad = jnp.concatenate([x, jnp.zeros((1, 1024), x.dtype)])[
            d.row_src]
        y_pad = moe.moe_expert_ffn_reference(
            x_pad, up, down, d.tile_group, d.n_tiles, tm=tm,
            activation="relu2")
        wm = jnp.where(idx < 128, w, 0.0)
        want = (wm[..., None] * y_pad[d.pos]).sum(1)
        assert int(sizes.sum()) == int((idx < 128).sum())
        assert self._rel(got, want) < 0.01


class TestSparseSelectionOnChip:
    """The decode step's top-2,048 (``dsa_topk_decode``, Mosaic) against
    the stable sort at GLM-5's served shape (24 slots of 53,248
    positions, live rows of 1 to 49k tokens, dead rows, NaN past each
    length) and over ties: the same kept set, in position order."""

    S, K = 53248, 2048

    def _check(self, s, cur, lens, pay):
        from bigdl_tpu.llm.kernels import sparse_attention as sa
        args = (jnp.asarray(s), jnp.asarray(cur), jnp.asarray(lens),
                jnp.asarray(pay))
        got, ok = sa.dsa_topk_decode(*args, k=self.K)
        want, wok = sa.dsa_select_reference(*args, k=self.K)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(wok))
        for r in range(len(lens)):
            g = np.asarray(got[r])[np.asarray(ok[r])]
            w = np.asarray(want[r])[np.asarray(wok[r])]
            assert sorted(g.tolist()) == sorted(w.tolist()), r
            order = np.argsort(pay[r])
            pos = order[np.searchsorted(pay[r][order], g)]
            assert (np.diff(pos) > 0).all(), r

    @pytest.mark.parametrize("scores", ["normal", "tenths", "flat_run"])
    def test_matches_the_sort(self, scores):
        rs = np.random.RandomState(3)
        lens = np.asarray([32000, 0, 22000, 2047, 2048, 49151, 1]
                          + [0] * 17, np.int32)
        s = rs.randn(len(lens), self.S).astype(np.float32)
        cur = rs.randn(len(lens)).astype(np.float32)
        if scores == "tenths":
            s, cur = np.round(s, 1), np.round(cur, 1)
        elif scores == "flat_run":
            s[:, 1000:30000] = 0.5
            cur[:] = 0.5
        for r, n in enumerate(lens):
            s[r, n:] = np.nan
        pay = rs.permutation(len(lens) * self.S).reshape(
            len(lens), self.S).astype(np.int32)
        self._check(s, cur, lens, pay)


class TestSparseFetchOnChip:
    """GLM-5's sparse latent attention (``sparse_latent_attention``,
    Mosaic), which fetches the selected rows from the pool itself, at
    the served widths (24 slots, 64 heads, rows 640 wide, 2,048
    selected) against float64 attention over the same rows gathered on
    the host: live rows of every kind beside dead ones."""

    B, H, W, DV, K = 24, 64, 640, 512, 2048

    def test_matches_attention_over_the_gathered_rows(self):
        from bigdl_tpu.llm.kernels import sparse_attention as sa
        rs = np.random.RandomState(5)
        pool = rs.randn(5 * 3001 * 16, self.W).astype(np.float32)
        pool = jnp.asarray(pool, jnp.bfloat16)
        rows = np.zeros((self.B, self.K), np.int32)
        counts = np.zeros((self.B, self.K), bool)
        # (kept entries, where the current token's -1 sits)
        kinds = [(2048, 2047), (2048, 700), (1, 0), (2047, 2046),
                 (40, None), (2048, None), (513, 512), (0, None)]
        for r, (keep, cur) in enumerate(kinds):
            # distinct rows, as a step's positions are; a run of eight
            # neighbours across a group where there are that many
            run = (8 * rs.randint(len(pool) // 8 - 1) + 4
                   + np.arange(min(keep, 8)))
            rest = rs.choice(np.setdiff1d(np.arange(len(pool)), run),
                             keep - len(run), replace=False)
            rows[r, :keep] = np.sort(np.concatenate([run, rest]))
            counts[r, :keep] = True
            if cur is not None:
                rows[r, cur], counts[r, cur] = -1, False
        q = rs.randn(self.B, self.H, self.W).astype(np.float32)
        sel = sa.gather_selected(pool.reshape(-1, self.W), jnp.asarray(rows))
        acc, m, l = jax.jit(functools.partial(
            sa.sparse_latent_stats, dv=self.DV, scale=0.0625))(
            jnp.asarray(q), sel, jnp.asarray(counts))
        kv = np.asarray(pool, np.float64)
        for r in range(self.B):
            live = counts[r]
            if not live.any():
                assert np.all(np.asarray(acc[r]) == 0)
                assert np.all(np.asarray(l[r]) == 0)
                assert np.all(np.asarray(m[r]) == -1e30)
                continue
            x = kv[rows[r][live]]
            s = q[r].astype(np.float64) @ x.T * 0.0625
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p @ x[:, :self.DV]) / p.sum(-1, keepdims=True)
            got = np.asarray(acc[r]) / np.asarray(l[r])[:, None]
            rel = np.abs(got - want).max() / np.abs(want).max()
            assert rel < 0.01, (r, rel)
