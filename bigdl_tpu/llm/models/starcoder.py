"""StarCoder / GPTBigCode family on TPU (ref: P:llm/ggml/model/starcoder
— the fourth of the reference's five ggml model families; SURVEY.md
§2.8 row 65). Distinct from the other stacks: **multi-query attention**
(ONE shared K/V head), learned absolute position embeddings (wpe, no
rotary), GPT-2-style LayerNorm+bias blocks, tanh-GELU MLP, tied head.

Same TPU-first skeleton: scan-stacked decoder layers, static ring kv
cache updated in-program, q4_0 quantized linears on the Pallas kernel.
MQA needs no special kernel — the shared :func:`llama._attention`
groups all ``Hq`` query heads onto the single kv head (GQA with
``g = Hq``), so repeated K/V never materializes."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.llm.models._facade import CausalLMFacade
from bigdl_tpu.llm.models.gptneox import _layer_norm, _linear_b
from bigdl_tpu.llm.models.llama import _attention


@dataclasses.dataclass
class StarCoderConfig:
    vocab_size: int = 49152
    hidden_size: int = 6144
    intermediate_size: int = 24576
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 1           # multi-query
    max_position_embeddings: int = 8192
    layer_norm_epsilon: float = 1e-5
    attn_block_size: int = 1024
    sliding_window = None                  # read by the shared _attention
    num_experts = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def starcoder_15b(cls) -> "StarCoderConfig":
        return cls()

    @classmethod
    def tiny(cls, vocab: int = 256) -> "StarCoderConfig":
        return cls(vocab_size=vocab, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   max_position_embeddings=128)

    @classmethod
    def from_hf(cls, hf) -> "StarCoderConfig":
        g = (lambda k, d: getattr(hf, k, d))
        return cls(vocab_size=g("vocab_size", 49152),
                   hidden_size=g("n_embd", 6144),
                   intermediate_size=g("n_inner", None)
                   or 4 * g("n_embd", 6144),
                   num_hidden_layers=g("n_layer", 40),
                   num_attention_heads=g("n_head", 48),
                   num_key_value_heads=(1 if g("multi_query", True)
                                        else g("n_head", 48)),
                   max_position_embeddings=g("n_positions", 8192),
                   layer_norm_epsilon=g("layer_norm_epsilon", 1e-5))


_LAYER_LINEARS = ("q_proj", "k_proj", "v_proj", "o_proj",
                  "fc_in", "fc_out")


def linear_shapes(cfg: StarCoderConfig) -> Dict[str, Tuple[int, int]]:
    h = cfg.hidden_size
    kv = cfg.num_key_value_heads * cfg.head_dim
    return {"q_proj": (h, h), "k_proj": (kv, h), "v_proj": (kv, h),
            "o_proj": (h, h), "fc_in": (cfg.intermediate_size, h),
            "fc_out": (h, cfg.intermediate_size)}


def init_params(cfg: StarCoderConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    key = jax.random.PRNGKey(seed)
    h = cfg.hidden_size
    L = cfg.num_hidden_layers
    shapes = linear_shapes(cfg)

    def mk(key, shape, scale=None):
        scale = scale or (1.0 / np.sqrt(shape[-1]))
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    keys = jax.random.split(key, 5 + len(shapes))
    layers: Dict[str, Any] = {}
    for i, (name, shape) in enumerate(shapes.items()):
        layers[name] = {"w": mk(keys[i], (L,) + shape),
                        "b": jnp.zeros((L, shape[0]), dtype)}
    for norm in ("input_layernorm", "post_attention_layernorm"):
        layers[norm] = {"w": jnp.ones((L, h), dtype),
                        "b": jnp.zeros((L, h), dtype)}
    return {
        "wte": mk(keys[-3], (cfg.vocab_size, h), 0.02),
        "wpe": mk(keys[-4], (cfg.max_position_embeddings, h), 0.02),
        "ln_f": {"w": jnp.ones((h,), dtype), "b": jnp.zeros((h,), dtype)},
        "layers": layers,
    }


def quantize_params(params: Dict[str, Any], qtype: str = "sym_int4"
                    ) -> Dict[str, Any]:
    from bigdl_tpu.llm.kernels import quantize_tpu

    if qtype != "sym_int4":
        raise NotImplementedError(
            "the scanned decoder path implements q4_0 (sym_int4)")
    out = dict(params)
    layers = dict(params["layers"])
    for name in _LAYER_LINEARS:
        w = np.asarray(layers[name]["w"], np.float32)
        if w.shape[1] % 128:
            # the MQA k/v projections are (head_dim, h) = (128, h) at
            # production size but smaller in test configs — tiny N stays
            # dense (the kernel tiles N at 128)
            continue
        qs, ss = [], []
        for l in range(w.shape[0]):
            qd = quantize_tpu(w[l], qtype)
            qs.append(qd["q"])
            ss.append(qd["scale"])
        layers[name] = {"q": jnp.asarray(np.stack(qs)),
                        "scale": jnp.asarray(np.stack(ss)),
                        "b": layers[name]["b"]}
    out["layers"] = layers
    return out


def init_cache(cfg: StarCoderConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    shape = (cfg.num_hidden_layers, batch, max_len,
             cfg.num_key_value_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def forward(params: Dict[str, Any], cfg: StarCoderConfig,
            tokens: jnp.ndarray, cache: Dict[str, jnp.ndarray],
            positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    # learned absolute position embeddings — the family's position story
    x = params["wte"][tokens] + params["wpe"][positions].astype(
        params["wte"].dtype)
    start = cache["pos"]
    s_max = cache["k"].shape[2]
    valid = jnp.arange(s_max)[None, :] < (start + tokens.shape[1])
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    kvh = cfg.num_key_value_heads

    def layer_step(carry, inputs):
        x, = carry
        lp, k_cache, v_cache = inputs
        b, t, _ = x.shape
        h1 = _layer_norm(x, lp["input_layernorm"], cfg.layer_norm_epsilon)
        q = _linear_b(lp["q_proj"], h1).reshape(b, t, nh, hd)
        k = _linear_b(lp["k_proj"], h1).reshape(b, t, kvh, hd)
        v = _linear_b(lp["v_proj"], h1).reshape(b, t, kvh, hd)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, start, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, start, 0, 0))
        attn = _attention(q, k_cache, v_cache, positions, valid, cfg)
        x = x + _linear_b(lp["o_proj"], attn)
        h2 = _layer_norm(x, lp["post_attention_layernorm"],
                         cfg.layer_norm_epsilon)
        mlp = _linear_b(lp["fc_out"], jax.nn.gelu(
            _linear_b(lp["fc_in"], h2).astype(jnp.float32),
            approximate=True).astype(x.dtype))   # gelu_pytorch_tanh
        x = x + mlp
        return (x,), (k_cache, v_cache)

    (x,), (k_new, v_new) = jax.lax.scan(
        layer_step, (x,), (params["layers"], cache["k"], cache["v"]))
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_epsilon)
    logits = x @ params["wte"].T.astype(x.dtype)   # tied head
    return logits.astype(jnp.float32), {
        "k": k_new, "v": v_new, "pos": start + tokens.shape[1]}


def paged_decode_step(params, cfg, k_pages, v_pages, bt, lens, toks,
                      *, page: int):
    """StarCoder paged-KV decode step — learned position embeddings,
    MQA (the paged stats kernel's GQA grouping handles Hkv=1), LN with
    bias, sequential residual, tied head; same structure as
    llama.paged_decode_step (rolled scan, read-only pools, one
    post-scan scatter). Lets the paged LLMServer serve GPTBigCode."""
    from bigdl_tpu.llm.kvcache.prefill import paged_attend
    from bigdl_tpu.llm.kvcache.write import scatter_new_kv
    b = toks.shape[0]
    L = cfg.num_hidden_layers
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    kvh = cfg.num_key_value_heads
    positions = lens[:, None].astype(jnp.int32)
    x = (params["wte"][toks][:, None]
         + params["wpe"][positions].astype(params["wte"].dtype))
    attend = paged_attend(k_pages, v_pages, bt, lens, page=page)

    def layer_step(carry, inputs):
        x, = carry
        lp, l = inputs
        h1 = _layer_norm(x, lp["input_layernorm"], cfg.layer_norm_epsilon)
        q = _linear_b(lp["q_proj"], h1).reshape(b, 1, nh, hd)
        k = _linear_b(lp["k_proj"], h1).reshape(b, 1, kvh, hd)
        v = _linear_b(lp["v_proj"], h1).reshape(b, 1, kvh, hd)
        attn = attend(l, q, k, v).astype(x.dtype)
        x = x + _linear_b(lp["o_proj"], attn.reshape(b, 1, -1))
        h2 = _layer_norm(x, lp["post_attention_layernorm"],
                         cfg.layer_norm_epsilon)
        mlp = _linear_b(lp["fc_out"], jax.nn.gelu(
            _linear_b(lp["fc_in"], h2).astype(jnp.float32),
            approximate=True).astype(x.dtype))
        x = x + mlp
        return (x,), (k[:, 0], v[:, 0])

    (x,), (k_new, v_new) = jax.lax.scan(
        layer_step, (x,), (params["layers"], jnp.arange(L)))
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_epsilon)
    logits = x @ params["wte"].T.astype(x.dtype)
    k_pages, v_pages = scatter_new_kv(k_pages, v_pages, bt, lens,
                                      k_new, v_new, page=page)
    return logits[:, 0].astype(jnp.float32), k_pages, v_pages


def paged_prefill_ragged(params, cfg, k_pages, v_pages, toks, length,
                         offset, bt_row, phys, slots, fork_dst,
                         fork_src, *, page: int,
                         full_logits: bool = False):
    """Ragged in-place prefill (ISSUE 8) — StarCoder's layer math
    (learned position embeddings, MQA via the kernel's GQA grouping,
    sequential residual, tied head) over the suffix tokens, attention
    reading the cached prefix in place; COW fork + one post-scan
    scatter fused into the same dispatch (see llama.paged_prefill_ragged
    for the structure and the ``full_logits`` speculative-verify
    variant)."""
    from bigdl_tpu.llm.kvcache.prefill import (fork_tail_pages,
                                               ragged_prefill_attend,
                                               scatter_suffix_kv)
    b, bucket = toks.shape                                  # b == 1
    L = cfg.num_hidden_layers
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    kvh = cfg.num_key_value_heads
    k_pages, v_pages = fork_tail_pages(k_pages, v_pages, fork_dst,
                                       fork_src)
    positions = (offset
                 + jnp.arange(bucket, dtype=jnp.int32))[None]  # (1, Tq)
    x = (params["wte"][toks]
         + params["wpe"][positions].astype(params["wte"].dtype))
    attend = ragged_prefill_attend(k_pages, v_pages, bt_row, offset,
                                   length, page=page)

    def layer_step(carry, inputs):
        x, = carry
        lp, l = inputs
        h1 = _layer_norm(x, lp["input_layernorm"], cfg.layer_norm_epsilon)
        q = _linear_b(lp["q_proj"], h1).reshape(b, bucket, nh, hd)
        k = _linear_b(lp["k_proj"], h1).reshape(b, bucket, kvh, hd)
        v = _linear_b(lp["v_proj"], h1).reshape(b, bucket, kvh, hd)
        # pool-precision K/V before attention (bit-parity with
        # generate() — see llama.paged_prefill_ragged)
        k = k.astype(k_pages.dtype)
        v = v.astype(v_pages.dtype)
        attn = attend(l, q, k, v).astype(x.dtype)
        x = x + _linear_b(lp["o_proj"], attn.reshape(b, bucket, -1))
        h2 = _layer_norm(x, lp["post_attention_layernorm"],
                         cfg.layer_norm_epsilon)
        mlp = _linear_b(lp["fc_out"], jax.nn.gelu(
            _linear_b(lp["fc_in"], h2).astype(jnp.float32),
            approximate=True).astype(x.dtype))
        x = x + mlp
        return (x,), (k[0], v[0])

    (x,), (k_new, v_new) = jax.lax.scan(
        layer_step, (x,), (params["layers"], jnp.arange(L)))
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_epsilon)
    logits = x @ params["wte"].T.astype(x.dtype)
    k_pages, v_pages = scatter_suffix_kv(k_pages, v_pages, phys, slots,
                                         k_new, v_new)
    if full_logits:
        return k_pages, v_pages, logits[0].astype(jnp.float32)
    last = jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0,
                                        keepdims=False)
    return k_pages, v_pages, last.astype(jnp.float32)


class StarCoderForCausalLM(CausalLMFacade):
    """Generation facade — shared driver (see models._facade)."""

    _forward = staticmethod(forward)
    _init_cache = staticmethod(init_cache)
    _init_params = staticmethod(init_params)
    _quantize_params = staticmethod(quantize_params)


# ---------------------------------------------------------------------------
# HF interop (safetensors, no torch)
# ---------------------------------------------------------------------------

def load_hf_starcoder_safetensors(path: str,
                                  cfg: Optional[StarCoderConfig] = None,
                                  qtype: Optional[str] = None,
                                  dtype=jnp.bfloat16) -> Dict[str, Any]:
    """HF GPTBigCodeForCausalLM checkpoint → our stacked layout. HF's
    ``attn.c_attn`` is a plain concat [q (h); k (kv); v (kv)] along the
    output dim (nn.Linear, NOT gpt2's transposed Conv1D)."""
    import json as _json
    import os as _os

    from bigdl_tpu.llm.kernels import quantize_tpu

    if qtype and qtype != "sym_int4":
        raise NotImplementedError("q4_0 only on the scanned path")
    if cfg is None:
        with open(_os.path.join(path, "config.json")) as f:
            raw = _json.load(f)
        cfg = StarCoderConfig.from_hf(type("HFConfig", (), raw)())

    from bigdl_tpu.llm.transformers.st_reader import SafetensorsReader
    reader = SafetensorsReader(path)   # handles the optional
    get = reader.get                   # "transformer." name prefix

    L = cfg.num_hidden_layers
    h = cfg.hidden_size
    kv = cfg.num_key_value_heads * cfg.head_dim
    _HF_LIN = {"o_proj": "attn.c_proj", "fc_in": "mlp.c_fc",
               "fc_out": "mlp.c_proj"}
    acc: Dict[str, Dict[str, list]] = {
        n: {"w": [], "q": [], "scale": [], "b": []} for n in _LAYER_LINEARS}

    def put_linear(name, w, b):
        a = acc[name]
        a["b"].append(b)
        if qtype and w.shape[0] % 128 == 0:
            qd = quantize_tpu(w, qtype)
            a["q"].append(qd["q"])
            a["scale"].append(qd["scale"])
        else:
            a["w"].append(w.astype(np.float32))

    for l in range(L):
        w = get(f"h.{l}.attn.c_attn.weight")
        b = get(f"h.{l}.attn.c_attn.bias")
        put_linear("q_proj", w[:h], b[:h])
        put_linear("k_proj", w[h:h + kv], b[h:h + kv])
        put_linear("v_proj", w[h + kv:], b[h + kv:])
        for name, hf in _HF_LIN.items():
            put_linear(name, get(f"h.{l}.{hf}.weight"),
                       get(f"h.{l}.{hf}.bias"))

    layers: Dict[str, Any] = {}
    for name, a in acc.items():
        entry: Dict[str, Any] = {"b": jnp.asarray(np.stack(a["b"]), dtype)}
        if a["q"]:
            entry["q"] = jnp.asarray(np.stack(a["q"]))
            entry["scale"] = jnp.asarray(np.stack(a["scale"]))
        else:
            entry["w"] = jnp.asarray(np.stack(a["w"]), dtype)
        layers[name] = entry
    for ours, hf in (("input_layernorm", "ln_1"),
                     ("post_attention_layernorm", "ln_2")):
        layers[ours] = {
            "w": jnp.asarray(np.stack(
                [get(f"h.{l}.{hf}.weight") for l in range(L)]), dtype),
            "b": jnp.asarray(np.stack(
                [get(f"h.{l}.{hf}.bias") for l in range(L)]), dtype)}
    return {
        "wte": jnp.asarray(get("wte.weight"), dtype),
        "wpe": jnp.asarray(get("wpe.weight"), dtype),
        "ln_f": {"w": jnp.asarray(get("ln_f.weight"), dtype),
                 "b": jnp.asarray(get("ln_f.bias"), dtype)},
        "layers": layers,
    }
