"""GPT-NeoX family on TPU (ref: P:llm/ggml/model/gptneox — the reference
ships five ggml model families; round 1 shipped Llama only. GPT-NeoX is
architecturally distinct from Llama: LayerNorm with bias (not RMSNorm),
biased linears, **parallel residual** (x + attn(ln1 x) + mlp(ln2 x)),
partial rotary embedding (``rotary_pct`` of head dims), GELU MLP, no GQA).

Same TPU-first skeleton as llama.py: scan-stacked decoder layers, static
ring kv cache updated in-program, q4_0 quantized linears dispatching to
the Pallas kernel on TPU, TP PartitionSpecs over ``model``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bigdl_tpu.llm.models._facade import CausalLMFacade
from bigdl_tpu.llm.models.llama import _attention, _linear


@dataclasses.dataclass
class GptNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 6144
    intermediate_size: int = 24576
    num_hidden_layers: int = 44
    num_attention_heads: int = 64
    rotary_pct: float = 0.25
    rotary_emb_base: float = 10000.0
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    attn_block_size: int = 1024
    sliding_window = None          # read by the shared _attention
    # GQA-free family
    @property
    def num_key_value_heads(self) -> int:
        return self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def pythia_70m(cls) -> "GptNeoXConfig":
        return cls(vocab_size=50304, hidden_size=512, intermediate_size=2048,
                   num_hidden_layers=6, num_attention_heads=8)

    @classmethod
    def tiny(cls, vocab: int = 256) -> "GptNeoXConfig":
        return cls(vocab_size=vocab, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   max_position_embeddings=128)

    @classmethod
    def from_hf(cls, hf) -> "GptNeoXConfig":
        g = (lambda k, d: getattr(hf, k, d))
        return cls(
            vocab_size=g("vocab_size", 50432),
            hidden_size=g("hidden_size", 6144),
            intermediate_size=g("intermediate_size", 24576),
            num_hidden_layers=g("num_hidden_layers", 44),
            num_attention_heads=g("num_attention_heads", 64),
            rotary_pct=g("rotary_pct", 0.25),
            rotary_emb_base=g("rotary_emb_base", 10000.0),
            max_position_embeddings=g("max_position_embeddings", 2048),
            layer_norm_eps=g("layer_norm_eps", 1e-5),
            use_parallel_residual=g("use_parallel_residual", True))


_LAYER_LINEARS = ("q_proj", "k_proj", "v_proj", "o_proj",
                  "fc_in", "fc_out")


def linear_shapes(cfg: GptNeoXConfig) -> Dict[str, Tuple[int, int]]:
    h = cfg.hidden_size
    return {
        "q_proj": (h, h), "k_proj": (h, h), "v_proj": (h, h),
        "o_proj": (h, h),
        "fc_in": (cfg.intermediate_size, h),
        "fc_out": (h, cfg.intermediate_size),
    }


def init_params(cfg: GptNeoXConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    key = jax.random.PRNGKey(seed)
    h = cfg.hidden_size
    L = cfg.num_hidden_layers
    shapes = linear_shapes(cfg)

    def mk(key, shape, scale=None):
        scale = scale or (1.0 / np.sqrt(shape[-1]))
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    keys = jax.random.split(key, 4 + len(shapes))
    layers: Dict[str, Any] = {}
    for i, (name, shape) in enumerate(shapes.items()):
        layers[name] = {"w": mk(keys[i], (L,) + shape),
                        "b": jnp.zeros((L, shape[0]), dtype)}
    for norm in ("input_layernorm", "post_attention_layernorm"):
        layers[norm] = {"w": jnp.ones((L, h), dtype),
                        "b": jnp.zeros((L, h), dtype)}
    return {
        "embed_in": mk(keys[-3], (cfg.vocab_size, h), 0.02),
        "final_norm": {"w": jnp.ones((h,), dtype),
                       "b": jnp.zeros((h,), dtype)},
        "embed_out": {"w": mk(keys[-2], (cfg.vocab_size, h))},
        "layers": layers,
    }


def quantize_params(params: Dict[str, Any], qtype: str = "sym_int4"
                    ) -> Dict[str, Any]:
    """ggml-quantize the decoder linears into the k-major TPU kernel
    layout (weights only; biases stay bf16)."""
    from bigdl_tpu.llm.kernels import quantize_tpu

    if qtype != "sym_int4":
        raise NotImplementedError(
            "the scanned decoder path implements q4_0 (sym_int4)")
    out = dict(params)
    layers = dict(params["layers"])
    for name in _LAYER_LINEARS:
        w = np.asarray(layers[name]["w"], np.float32)
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


def param_pspecs(params: Dict[str, Any]) -> Dict[str, Any]:
    """Megatron TP rules over ``model``: q/k/v and fc_in row-sharded,
    o_proj/fc_out col-sharded, embeddings vocab-sharded, norms/biases of
    col-sharded layers replicated."""
    ROW = {"q_proj", "k_proj", "v_proj", "fc_in"}

    def spec_for(path, leaf):
        keys = [str(getattr(p, "key", "")) for p in path]
        stacked = "layers" in keys
        d0 = 1 if stacked else 0
        name = next((k for k in keys if k in ROW
                     or k in ("o_proj", "fc_out", "embed_in",
                              "embed_out")), None)
        if name is None or getattr(leaf, "ndim", 0) <= d0:
            return P()
        is_bias = keys[-1] == "b"
        kmajor = keys[-1] in ("q", "scale", "zero")   # TPU k-major layout
        spec = [None] * leaf.ndim
        if name in ROW or name in ("embed_in", "embed_out"):
            if kmajor:
                spec[-1] = "model"           # N is the last dim
            else:
                spec[d0] = "model"           # bias of a row-sharded linear
                # shards with it (dim d0 is the output dim for both)
        elif not is_bias:                    # o_proj / fc_out weights: K dim
            if kmajor:
                spec[d0] = "model"
            elif leaf.ndim > d0 + 1:
                spec[d0 + 1] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _layer_norm(x, wd, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y.astype(x.dtype) * wd["w"].astype(x.dtype)
            + wd["b"].astype(x.dtype))


def _linear_b(wd, x):
    y = _linear({k: v for k, v in wd.items() if k != "b"}, x)
    return y + wd["b"].astype(y.dtype)


def _partial_rope(x, positions, cfg: GptNeoXConfig):
    """Rotate only the first ``rotary_pct`` of head dims (HF convention:
    interleaved-free rotate_half on the rotary slice)."""
    d = x.shape[-1]
    rot = int(d * cfg.rotary_pct)
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv = 1.0 / (cfg.rotary_emb_base
                 ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv     # (B,T,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin,
                               x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)


def init_cache(cfg: GptNeoXConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    shape = (cfg.num_hidden_layers, batch, max_len,
             cfg.num_attention_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def forward(params: Dict[str, Any], cfg: GptNeoXConfig,
            tokens: jnp.ndarray, cache: Dict[str, jnp.ndarray],
            positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    x = params["embed_in"][tokens]
    start = cache["pos"]
    s_max = cache["k"].shape[2]
    valid = jnp.arange(s_max)[None, :] < (start + tokens.shape[1])
    nh, hd = cfg.num_attention_heads, cfg.head_dim

    def layer_step(carry, inputs):
        x, = carry
        lp, k_cache, v_cache = inputs
        b, t, _ = x.shape
        h1 = _layer_norm(x, lp["input_layernorm"], cfg.layer_norm_eps)
        q = _linear_b(lp["q_proj"], h1).reshape(b, t, nh, hd)
        k = _linear_b(lp["k_proj"], h1).reshape(b, t, nh, hd)
        v = _linear_b(lp["v_proj"], h1).reshape(b, t, nh, hd)
        q = _partial_rope(q, positions, cfg)
        k = _partial_rope(k, positions, cfg)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, start, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, start, 0, 0))
        attn = _attention(q, k_cache, v_cache, positions, valid, cfg)
        attn = _linear_b(lp["o_proj"], attn)
        h2_in = x if cfg.use_parallel_residual else x + attn
        h2 = _layer_norm(h2_in, lp["post_attention_layernorm"],
                         cfg.layer_norm_eps)
        mlp = _linear_b(lp["fc_out"], jax.nn.gelu(
            _linear_b(lp["fc_in"], h2).astype(jnp.float32),
            approximate=False).astype(x.dtype))
        if cfg.use_parallel_residual:
            x = x + attn + mlp
        else:
            x = h2_in + mlp
        return (x,), (k_cache, v_cache)

    (x,), (k_new, v_new) = jax.lax.scan(
        layer_step, (x,), (params["layers"], cache["k"], cache["v"]))
    x = _layer_norm(x, params["final_norm"], cfg.layer_norm_eps)
    logits = _linear(params["embed_out"], x)
    return logits.astype(jnp.float32), {
        "k": k_new, "v": v_new, "pos": start + tokens.shape[1]}


def paged_decode_step(params, cfg, k_pages, v_pages, bt, lens, toks,
                      *, page: int):
    """GPT-NeoX paged-KV decode step — the family's layer math (LN with
    bias, biased linears, partial rotary, PARALLEL residual) in the
    same structure as llama.paged_decode_step: rolled layer scan,
    read-only pools (stats kernel + flash merge of the current token),
    one post-scan scatter into the donated pools. Lets the paged
    continuous-batching LLMServer serve the NeoX family."""
    from bigdl_tpu.llm.kvcache.prefill import paged_attend
    from bigdl_tpu.llm.kvcache.write import scatter_new_kv
    b = toks.shape[0]
    L = cfg.num_hidden_layers
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    x = params["embed_in"][toks][:, None]                     # (B, 1, H)
    positions = lens[:, None].astype(jnp.int32)
    attend = paged_attend(k_pages, v_pages, bt, lens, page=page)

    def layer_step(carry, inputs):
        x, = carry
        lp, l = inputs
        h1 = _layer_norm(x, lp["input_layernorm"], cfg.layer_norm_eps)
        q = _linear_b(lp["q_proj"], h1).reshape(b, 1, nh, hd)
        k = _linear_b(lp["k_proj"], h1).reshape(b, 1, nh, hd)
        v = _linear_b(lp["v_proj"], h1).reshape(b, 1, nh, hd)
        q = _partial_rope(q, positions, cfg)
        k = _partial_rope(k, positions, cfg)
        attn = attend(l, q, k, v).astype(x.dtype)
        attn = _linear_b(lp["o_proj"], attn.reshape(b, 1, -1))
        h2_in = x if cfg.use_parallel_residual else x + attn
        h2 = _layer_norm(h2_in, lp["post_attention_layernorm"],
                         cfg.layer_norm_eps)
        mlp = _linear_b(lp["fc_out"], jax.nn.gelu(
            _linear_b(lp["fc_in"], h2).astype(jnp.float32),
            approximate=False).astype(x.dtype))
        if cfg.use_parallel_residual:
            x = x + attn + mlp
        else:
            x = h2_in + mlp
        return (x,), (k[:, 0], v[:, 0])

    (x,), (k_new, v_new) = jax.lax.scan(
        layer_step, (x,), (params["layers"], jnp.arange(L)))
    x = _layer_norm(x, params["final_norm"], cfg.layer_norm_eps)
    logits = _linear(params["embed_out"], x)
    k_pages, v_pages = scatter_new_kv(k_pages, v_pages, bt, lens,
                                      k_new, v_new, page=page)
    return logits[:, 0].astype(jnp.float32), k_pages, v_pages


def paged_prefill_ragged(params, cfg, k_pages, v_pages, toks, length,
                         offset, bt_row, phys, slots, fork_dst,
                         fork_src, *, page: int,
                         full_logits: bool = False):
    """Ragged in-place prefill (ISSUE 8) — the NeoX layer math (LN with
    bias, partial rotary, parallel residual) over the suffix tokens,
    attention reading the cached prefix in place via the ragged kernel;
    COW fork + one post-scan scatter fused into the same dispatch (see
    llama.paged_prefill_ragged for the structure and the
    ``full_logits`` speculative-verify variant)."""
    from bigdl_tpu.llm.kvcache.prefill import (fork_tail_pages,
                                               ragged_prefill_attend,
                                               scatter_suffix_kv)
    b, bucket = toks.shape                                  # b == 1
    L = cfg.num_hidden_layers
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    k_pages, v_pages = fork_tail_pages(k_pages, v_pages, fork_dst,
                                       fork_src)
    positions = (offset
                 + jnp.arange(bucket, dtype=jnp.int32))[None]  # (1, Tq)
    x = params["embed_in"][toks]
    attend = ragged_prefill_attend(k_pages, v_pages, bt_row, offset,
                                   length, page=page)

    def layer_step(carry, inputs):
        x, = carry
        lp, l = inputs
        h1 = _layer_norm(x, lp["input_layernorm"], cfg.layer_norm_eps)
        q = _linear_b(lp["q_proj"], h1).reshape(b, bucket, nh, hd)
        k = _linear_b(lp["k_proj"], h1).reshape(b, bucket, nh, hd)
        v = _linear_b(lp["v_proj"], h1).reshape(b, bucket, nh, hd)
        q = _partial_rope(q, positions, cfg)
        k = _partial_rope(k, positions, cfg)
        # pool-precision K/V before attention (bit-parity with
        # generate() — see llama.paged_prefill_ragged)
        k = k.astype(k_pages.dtype)
        v = v.astype(v_pages.dtype)
        attn = attend(l, q, k, v).astype(x.dtype)
        attn = _linear_b(lp["o_proj"], attn.reshape(b, bucket, -1))
        h2_in = x if cfg.use_parallel_residual else x + attn
        h2 = _layer_norm(h2_in, lp["post_attention_layernorm"],
                         cfg.layer_norm_eps)
        mlp = _linear_b(lp["fc_out"], jax.nn.gelu(
            _linear_b(lp["fc_in"], h2).astype(jnp.float32),
            approximate=False).astype(x.dtype))
        if cfg.use_parallel_residual:
            x = x + attn + mlp
        else:
            x = h2_in + mlp
        return (x,), (k[0], v[0])

    (x,), (k_new, v_new) = jax.lax.scan(
        layer_step, (x,), (params["layers"], jnp.arange(L)))
    x = _layer_norm(x, params["final_norm"], cfg.layer_norm_eps)
    logits = _linear(params["embed_out"], x)
    k_pages, v_pages = scatter_suffix_kv(k_pages, v_pages, phys, slots,
                                         k_new, v_new)
    if full_logits:
        return k_pages, v_pages, logits[0].astype(jnp.float32)
    last = jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0,
                                        keepdims=False)
    return k_pages, v_pages, last.astype(jnp.float32)


class GptNeoXForCausalLM(CausalLMFacade):
    """Generation facade — shared driver (see models._facade)."""

    _forward = staticmethod(forward)
    _init_cache = staticmethod(init_cache)
    _init_params = staticmethod(init_params)
    _quantize_params = staticmethod(quantize_params)

    def shard(self, mesh) -> "GptNeoXForCausalLM":
        """Place params on a mesh with TP PartitionSpecs."""
        from jax.sharding import NamedSharding

        specs = param_pspecs(self.params)
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            self.params, specs)
        return self


# ---------------------------------------------------------------------------
# HF interop (safetensors, no torch)
# ---------------------------------------------------------------------------

def load_hf_gptneox_safetensors(path: str,
                                cfg: Optional[GptNeoXConfig] = None,
                                qtype: Optional[str] = None,
                                dtype=jnp.bfloat16) -> Dict[str, Any]:
    """HF GPTNeoXForCausalLM checkpoint → our stacked layout. The HF
    layer fuses qkv as ``query_key_value`` with per-head interleaving
    [q1 k1 v1 q2 k2 v2 ...]; we split it back into separate projections."""
    import json as _json
    import os as _os

    from bigdl_tpu.llm.kernels import quantize_tpu

    if qtype and qtype != "sym_int4":
        raise NotImplementedError("q4_0 only on the scanned path")
    if cfg is None:
        with open(_os.path.join(path, "config.json")) as f:
            raw = _json.load(f)
        cfg = GptNeoXConfig.from_hf(type("HFConfig", (), raw)())

    # lazy per-tensor reads (same stream-per-layer pattern as the llama
    # loader): only one layer's tensors are resident at a time
    from bigdl_tpu.llm.transformers.st_reader import SafetensorsReader
    get = SafetensorsReader(path).get

    L = cfg.num_hidden_layers
    nh, hd, h = cfg.num_attention_heads, cfg.head_dim, cfg.hidden_size
    _HF_LIN = {"o_proj": "attention.dense", "fc_in": "mlp.dense_h_to_4h",
               "fc_out": "mlp.dense_4h_to_h"}
    # per-layer accumulators: only one layer's fp32 tensors live at a time
    acc: Dict[str, Dict[str, list]] = {
        n: {"w": [], "q": [], "scale": [], "b": []} for n in _LAYER_LINEARS}

    def put_linear(name, w, b):
        a = acc[name]
        a["b"].append(b)
        if qtype:
            qd = quantize_tpu(w, qtype)
            a["q"].append(qd["q"])
            a["scale"].append(qd["scale"])
        else:
            a["w"].append(w.astype(np.float32))

    for l in range(L):
        # fused qkv: (nh*(3*hd), h) output dim laid out [q k v] per head
        w = get(f"gpt_neox.layers.{l}.attention.query_key_value.weight")
        b = get(f"gpt_neox.layers.{l}.attention.query_key_value.bias")
        w = w.reshape(nh, 3, hd, h)
        b = b.reshape(nh, 3, hd)
        for i, name in enumerate(("q_proj", "k_proj", "v_proj")):
            put_linear(name, w[:, i].reshape(h, h), b[:, i].reshape(h))
        for name, hf in _HF_LIN.items():
            put_linear(name, get(f"gpt_neox.layers.{l}.{hf}.weight"),
                       get(f"gpt_neox.layers.{l}.{hf}.bias"))

    layers: Dict[str, Any] = {}
    for name, a in acc.items():
        entry: Dict[str, Any] = {"b": jnp.asarray(np.stack(a["b"]), dtype)}
        if qtype:
            entry["q"] = jnp.asarray(np.stack(a["q"]))
            entry["scale"] = jnp.asarray(np.stack(a["scale"]))
        else:
            entry["w"] = jnp.asarray(np.stack(a["w"]), dtype)
        layers[name] = entry
    for ours, hf in (("input_layernorm", "input_layernorm"),
                     ("post_attention_layernorm",
                      "post_attention_layernorm")):
        layers[ours] = {
            "w": jnp.asarray(np.stack(
                [get(f"gpt_neox.layers.{l}.{hf}.weight")
                 for l in range(L)]), dtype),
            "b": jnp.asarray(np.stack(
                [get(f"gpt_neox.layers.{l}.{hf}.bias")
                 for l in range(L)]), dtype)}
    return {
        "embed_in": jnp.asarray(get("gpt_neox.embed_in.weight"), dtype),
        "final_norm": {
            "w": jnp.asarray(get("gpt_neox.final_layer_norm.weight"),
                             dtype),
            "b": jnp.asarray(get("gpt_neox.final_layer_norm.bias"),
                             dtype)},
        "embed_out": {"w": jnp.asarray(get("embed_out.weight"), dtype)},
        "layers": layers,
    }
