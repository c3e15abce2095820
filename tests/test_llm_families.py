"""Round-5 model families — Bloom (ALiBi), StarCoder (MQA), ChatGLM/GLM
(interleaved partial rotary on the Llama stack). The reference ships
five ggml families (P:llm/ggml/model/, SURVEY.md §2.8 row 65); with
these the repo covers all five plus the transformers-path lineages.
Each family gets (a) an HF numerics cross-check through the public
AutoModelForCausalLM facade and (b) a quantized-generate smoke."""

import numpy as np
import pytest

import jax.numpy as jnp


def _save_hf(tmp_path, hf_model, name):
    path = str(tmp_path / name)
    hf_model.eval()
    hf_model.save_pretrained(path, safe_serialization=True)
    return path


class TestBloom:
    def _tiny_hf(self):
        torch = pytest.importorskip("torch")
        transformers = pytest.importorskip("transformers")
        cfg = transformers.BloomConfig(
            vocab_size=97, hidden_size=32, n_layer=2, n_head=4,
            use_cache=False)
        torch.manual_seed(0)
        return torch, transformers.BloomForCausalLM(cfg)

    def test_matches_hf_bloom_numerics(self, tmp_path):
        torch, hf = self._tiny_hf()
        path = _save_hf(tmp_path, hf, "tiny-bloom")
        from bigdl_tpu.llm.models.bloom import BloomForCausalLM
        from bigdl_tpu.llm.transformers import AutoModelForCausalLM
        model = AutoModelForCausalLM.from_pretrained(path, max_cache_len=32)
        assert isinstance(model, BloomForCausalLM)
        ids = np.array([[3, 17, 42, 9, 60]], np.int64)
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.float().numpy()
        logits, _ = model(jnp.asarray(ids, jnp.int32))
        ours = np.asarray(logits)
        np.testing.assert_allclose(ours, ref, rtol=0.1, atol=0.1)
        assert (np.argmax(ours[:, -1], -1)
                == np.argmax(ref[:, -1], -1)).all()

    def test_alibi_slopes_match_hf(self):
        torch = pytest.importorskip("torch")
        from transformers.models.bloom.modeling_bloom import (
            build_alibi_tensor)
        from bigdl_tpu.llm.models.bloom import alibi_slopes
        for n in (4, 8, 6, 12):   # powers of 2 and not
            mask = torch.ones(1, 5)
            al = build_alibi_tensor(mask, n, torch.float32)
            # hf alibi (1*n, 1, 5): slope = al[h, 0, 1] (key index 1)
            hf_slopes = al.reshape(n, 5)[:, 1].numpy()
            np.testing.assert_allclose(alibi_slopes(n), hf_slopes,
                                       rtol=1e-6)

    def test_quantized_generate(self):
        from bigdl_tpu.llm.models.bloom import (BloomConfig,
                                                BloomForCausalLM)
        import dataclasses
        cfg = dataclasses.replace(BloomConfig.tiny(), hidden_size=256,
                                  num_attention_heads=2)
        model = BloomForCausalLM.from_config(cfg, seed=0,
                                             load_in_low_bit="sym_int4",
                                             max_cache_len=32)
        lp = model.params["layers"]["q_proj"]
        assert "q" in lp and "scale" in lp
        out = model.generate(np.array([[1, 5, 9]], np.int32),
                             max_new_tokens=6)
        assert out.shape == (1, 9)

    def test_prefill_decode_consistency(self):
        """ALiBi positions must agree between one-shot prefill and
        step-wise decode (the shift-invariant bias form)."""
        from bigdl_tpu.llm.models.bloom import (BloomConfig, forward,
                                                init_cache, init_params)
        cfg = BloomConfig.tiny()
        params = init_params(cfg, seed=0, dtype=jnp.float32)
        toks = np.array([[5, 9, 3, 7]], np.int32)
        cache = init_cache(cfg, 1, 16, dtype=jnp.float32)
        pos = jnp.arange(4)[None, :]
        full, _ = forward(params, cfg, jnp.asarray(toks), cache, pos)
        cache = init_cache(cfg, 1, 16, dtype=jnp.float32)
        outs = []
        for t in range(4):
            lg, cache = forward(params, cfg,
                                jnp.asarray(toks[:, t:t + 1]), cache,
                                jnp.asarray([[t]]))
            outs.append(np.asarray(lg[:, 0]))
        np.testing.assert_allclose(np.asarray(full), np.stack(outs, 1),
                                   rtol=2e-2, atol=2e-2)


class TestStarCoder:
    def test_matches_hf_gpt_bigcode_numerics(self, tmp_path):
        torch = pytest.importorskip("torch")
        transformers = pytest.importorskip("transformers")
        cfg = transformers.GPTBigCodeConfig(
            vocab_size=97, n_embd=32, n_layer=2, n_head=4,
            n_positions=64, multi_query=True, use_cache=False)
        torch.manual_seed(0)
        hf = transformers.GPTBigCodeForCausalLM(cfg)
        path = _save_hf(tmp_path, hf, "tiny-bigcode")
        from bigdl_tpu.llm.models.starcoder import StarCoderForCausalLM
        from bigdl_tpu.llm.transformers import AutoModelForCausalLM
        model = AutoModelForCausalLM.from_pretrained(path, max_cache_len=32)
        assert isinstance(model, StarCoderForCausalLM)
        assert model.config.num_key_value_heads == 1   # MQA
        ids = np.array([[3, 17, 42, 9, 60]], np.int64)
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.float().numpy()
        logits, _ = model(jnp.asarray(ids, jnp.int32))
        ours = np.asarray(logits)
        np.testing.assert_allclose(ours, ref, rtol=0.1, atol=0.1)
        assert (np.argmax(ours[:, -1], -1)
                == np.argmax(ref[:, -1], -1)).all()

    def test_quantized_generate(self):
        from bigdl_tpu.llm.models.starcoder import (StarCoderConfig,
                                                    StarCoderForCausalLM)
        import dataclasses
        cfg = dataclasses.replace(StarCoderConfig.tiny(), hidden_size=256,
                                  intermediate_size=256,
                                  num_attention_heads=2)
        model = StarCoderForCausalLM.from_config(
            cfg, seed=0, load_in_low_bit="sym_int4", max_cache_len=32)
        assert "q" in model.params["layers"]["q_proj"]
        # MQA k/v (head_dim=128, h) quantize too at this size
        assert "q" in model.params["layers"]["k_proj"]
        out = model.generate(np.array([[1, 5, 9]], np.int32),
                             max_new_tokens=6)
        assert out.shape == (1, 9)


class TestFamilyServing:
    """Round-5 tail: the paged continuous-batching LLMServer dispatches
    per family — GPT-NeoX and StarCoder get their own paged decode
    steps (same read-only-pool scan structure); Bloom is rejected with
    a clear error (ALiBi has no paged-kernel bias hook yet)."""

    @pytest.mark.parametrize("family", ["gptneox", "gptneox-seq",
                                        "starcoder"])
    def test_paged_server_greedy_parity(self, family):
        import dataclasses
        from bigdl_tpu.llm.serving import LLMServer
        if family.startswith("gptneox"):
            from bigdl_tpu.llm.models import (GptNeoXConfig as C,
                                              GptNeoXForCausalLM as M)
            cfg = C.tiny()
            if family == "gptneox-seq":
                # sequential-residual NeoX (early StableLM lineage):
                # pins the use_parallel_residual=False paged branch
                cfg = dataclasses.replace(cfg,
                                          use_parallel_residual=False)
        else:
            from bigdl_tpu.llm.models import (StarCoderConfig as C,
                                              StarCoderForCausalLM as M)
            cfg = C.tiny()
        model = M.from_config(cfg, seed=0, max_cache_len=64)
        prompt = [7, 3, 11, 2]
        want = model.generate(np.asarray([prompt], np.int32),
                              max_new_tokens=8)[0, len(prompt):]
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        try:
            got = srv.submit(prompt, max_new_tokens=8).get(180)
            # a second, different-length request through the same server
            got2 = srv.submit([5, 9], max_new_tokens=4).get(180)
        finally:
            srv.stop()
        assert list(got) == list(map(int, want))
        want2 = model.generate(np.asarray([[5, 9]], np.int32),
                               max_new_tokens=4)[0, 2:]
        assert list(got2) == list(map(int, want2))

    def test_bloom_serving_rejected_with_clear_error(self):
        from bigdl_tpu.llm.models import BloomConfig, BloomForCausalLM
        from bigdl_tpu.llm.serving import LLMServer
        model = BloomForCausalLM.from_config(BloomConfig.tiny(), seed=0,
                                             max_cache_len=32)
        with pytest.raises(NotImplementedError, match="paged decode"):
            LLMServer(model)

    def test_family_without_a_ragged_prefill_is_refused(self, monkeypatch):
        """A family is two programs. One that has a decode step and no
        ragged prefill is refused at construction, in the sentence that
        refuses bloom: the engine has no other prefill to serve it by."""
        from bigdl_tpu.llm.models import (StarCoderConfig,
                                          StarCoderForCausalLM, starcoder)
        from bigdl_tpu.llm.serving import LLMServer
        model = StarCoderForCausalLM.from_config(StarCoderConfig.tiny(),
                                                 seed=0, max_cache_len=32)
        monkeypatch.delattr(starcoder, "paged_prefill_ragged")
        with pytest.raises(NotImplementedError,
                           match="paged decode step and ragged prefill"):
            LLMServer(model)

    def test_model_layer_does_not_import_the_engine(self):
        """The arrows point down: models, kernels and the KV cache are
        what the engine is built from, so none of their files imports
        ``bigdl_tpu.llm.serving``, at module level or inside a
        function (where a cycle would hide)."""
        import ast
        import pathlib

        import bigdl_tpu.llm as llm
        root = pathlib.Path(llm.__file__).parent
        found = []
        for sub in ("models", "kernels", "kvcache"):
            for path in sorted((root / sub).rglob("*.py")):
                for node in ast.walk(ast.parse(path.read_text())):
                    if isinstance(node, ast.Import):
                        names = [a.name for a in node.names]
                    elif isinstance(node, ast.ImportFrom):
                        names = [node.module or ""] + [
                            f"{node.module}.{a.name}" for a in node.names]
                    else:
                        continue
                    found += [f"{path.relative_to(root)}:{node.lineno}"
                              for n in names
                              if n.startswith("bigdl_tpu.llm.serving")]
        assert not found, found


class TestChatGLM:
    def test_matches_hf_glm_numerics(self, tmp_path):
        """GLM-4 (HF ``glm``) is the transformers-native ChatGLM lineage:
        interleaved partial rotary + GQA + qkv biases + fused gate_up —
        implemented as a LlamaConfig rope_mode='glm' variant."""
        torch = pytest.importorskip("torch")
        transformers = pytest.importorskip("transformers")
        cfg = transformers.GlmConfig(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, partial_rotary_factor=0.5,
            attention_bias=True, max_position_embeddings=64,
            tie_word_embeddings=False, use_cache=False,
            pad_token_id=0, eos_token_id=1)
        torch.manual_seed(0)
        hf = transformers.GlmForCausalLM(cfg)
        path = _save_hf(tmp_path, hf, "tiny-glm")
        from bigdl_tpu.llm.models.llama import LlamaForCausalLM
        from bigdl_tpu.llm.transformers import AutoModelForCausalLM
        model = AutoModelForCausalLM.from_pretrained(path, max_cache_len=32)
        assert isinstance(model, LlamaForCausalLM)
        assert model.config.rope_mode == "glm"
        assert model.config.partial_rotary_factor == 0.5
        ids = np.array([[3, 17, 42, 9, 60]], np.int64)
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.float().numpy()
        logits, _ = model(jnp.asarray(ids, jnp.int32))
        ours = np.asarray(logits)
        np.testing.assert_allclose(ours, ref, rtol=0.1, atol=0.1)
        assert (np.argmax(ours[:, -1], -1)
                == np.argmax(ref[:, -1], -1)).all()

    def test_glm_serves_on_the_paged_server(self):
        """The GLM rotary variant must ride the paged continuous-batching
        server unchanged (rope_cfg dispatch inside paged_decode_step):
        served greedy tokens == generate() greedy tokens."""
        from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                                LlamaForCausalLM)
        from bigdl_tpu.llm.serving import LLMServer
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny_glm(),
                                             seed=0, max_cache_len=64)
        prompt = [7, 3, 11, 2]
        want = model.generate(np.asarray([prompt], np.int32),
                              max_new_tokens=8)[0, len(prompt):]
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        try:
            got = srv.submit(prompt, max_new_tokens=8).get(120)
        finally:
            srv.stop()
        assert list(got) == list(want)

    def test_quantized_generate(self):
        from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                                LlamaForCausalLM)
        import dataclasses
        cfg = dataclasses.replace(LlamaConfig.tiny_glm(), hidden_size=256,
                                  intermediate_size=256,
                                  num_attention_heads=2,
                                  num_key_value_heads=2)
        model = LlamaForCausalLM.from_config(
            cfg, seed=0, load_in_low_bit="sym_int4", max_cache_len=32)
        out = model.generate(np.array([[1, 5, 9]], np.int32),
                             max_new_tokens=6)
        assert out.shape == (1, 9)
