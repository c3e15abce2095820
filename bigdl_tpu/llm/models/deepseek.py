"""DeepSeek-V3-style decoders (``model_type`` ``deepseek_v3``) on TPU:
multi-head latent attention and sigmoid-routed experts with shared ones
(ISSUE 27; the configuration the chip runs is Kanana-2-30B-A3B).

Per layer, pre-norm residual, RMSNorm:

- **MLA** (no query down-projection). ``q = h W_q`` -> heads of
  ``qk_nope | qk_rope``; ``[c | k_r] = h W_kva`` -> ``kv_lora_rank |
  qk_rope``; ``c`` is normed; RoPE on ``q_rope`` of every head and on
  the one ``k_r`` all heads share. The checkpoint stores rope pairs
  interleaved ``(2i, 2i+1)``: they are de-interleaved to ``(i, i+d/2)``
  and then rotated by halves. ``[k_nope | v] = c W_kvb`` per head,
  scores ``q.k * (qk_nope + qk_rope)^-0.5``. **The cache holds
  ``[c | k_r]`` per token and layer, after the norm and the rotation,
  and nothing else.** Decode uses the absorbed form (``q_lat = q_nope
  W_uk^T`` scores the cached ``c`` directly, ``o = (P c) W_uv``), which
  is multi-query attention over one row a token whose first
  ``kv_lora_rank`` columns are also the value; prefill expands K and V
  from the chunk's own ``c``. The two are the same function.
- **Router**, float32: ``s = sigmoid(h W_g^T)``; the ``k`` largest of
  ``s + b`` are chosen; their weights are ``s`` (without ``b``) over
  their sum, times ``routed_scaling_factor``.
- **Experts**: ``y = sum_k w_k E_k(h) + E_shared(h)``, every assignment
  computed (:mod:`bigdl_tpu.llm.kernels.moe`): no capacity, no dropped
  token, no one-hot over the experts. The first
  ``first_k_dense_replace`` layers are a dense SwiGLU instead and stand
  outside the rolled scan over the expert layers.

Parameters (:func:`init_params`): ``dense_layers`` and ``layers`` are
stacked over their layers; ``experts`` holds ``w_gate_up`` ``(Lm, E+S,
H, 2I)`` and ``w_down`` ``(Lm, E+S, I, H)`` in x @ W layout, gate
columns then up columns, the shared expert of width ``S·I`` as the last
``S`` groups (its column blocks).

The paged engine's pool is ONE latent pool ``(L, P, 1, page, W)`` with
``W`` = 576 padded up to a multiple of the 128 lanes the kernel's page
DMA needs (640: ``c`` in columns 0-511, ``k_r`` in 512-575, zeros
after), written by ``kvcache/write.py`` and read by
``kernels.paged_attention.latent_attention_decode_stats``. There is no
V pool.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.llm.kernels import moe
from bigdl_tpu.llm.kernels.paged_attention import LANE
from bigdl_tpu.llm.models._facade import CausalLMFacade
from bigdl_tpu.llm.models.llama import _linear, mlp, rms_norm, rope

# queries of a prefill bucket attended at a time (expanded form): a
# (heads, 256, T) float32 score block is 134 MB at the 4,096 bucket
ATTN_QUERY_BLOCK = 256


@dataclasses.dataclass
class DeepseekConfig:
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Numbers cached per token and layer: ``c`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The pool's minor dimension: ``latent_dim`` lane-padded."""
        return -(-self.latent_dim // LANE) * LANE

    @property
    def attn_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def n_groups(self) -> int:
        """Groups of the expert product: routed, then shared blocks."""
        return self.n_routed_experts + self.n_shared_experts

    @classmethod
    def tiny(cls, vocab: int = 256) -> "DeepseekConfig":
        return cls(vocab_size=vocab, hidden_size=64, intermediate_size=128,
                   moe_intermediate_size=32, num_hidden_layers=3,
                   num_attention_heads=4, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   n_routed_experts=8, num_experts_per_tok=2,
                   n_shared_experts=1, max_position_embeddings=512)

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "DeepseekConfig":
        """From the keys of a ``deepseek_v3`` ``config.json``. What the
        equations above do not cover is refused by name."""
        g = hf.get
        unsupported = {
            "q_lora_rank": g("q_lora_rank") is not None,
            "rope_scaling": g("rope_scaling") is not None,
            "n_group/topk_group != 1":
                (g("n_group", 1), g("topk_group", 1)) != (1, 1),
            "scoring_func != sigmoid": g("scoring_func",
                                         "sigmoid") != "sigmoid",
            "moe_layer_freq != 1": g("moe_layer_freq", 1) != 1,
            "attention_bias": bool(g("attention_bias", False)),
            "tie_word_embeddings": bool(g("tie_word_embeddings", False)),
            "hidden_act != silu": g("hidden_act", "silu") != "silu",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"deepseek_v3 config uses {bad}, which this family does "
                "not implement")
        names = {f.name for f in dataclasses.fields(cls)}
        keys = {k: v for k, v in hf.items() if k in names and v is not None}
        keys["rope_theta"] = float(g("rope_theta", 1e6))
        return cls(**keys)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def linear_shapes(cfg: DeepseekConfig) -> Dict[str, Tuple[int, int]]:
    """``(N, K)`` of the attention linears every layer has."""
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    return {
        "q_proj": (nh * cfg.qk_head_dim, h),
        "kv_a_proj": (cfg.latent_dim, h),
        "kv_b_proj": (nh * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                      cfg.kv_lora_rank),
        "o_proj": (h, nh * cfg.v_head_dim),
    }


def init_params(cfg: DeepseekConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Seeded parameters, drawn where JAX's default device is: every
    linear zero-mean at unit gain (output rms = input rms), except that
    the projections back into the residual stream (``o_proj``, the
    dense ``down_proj``, the experts' ``w_down``) are scaled by
    ``1 / sqrt(2 L)`` as GPT-2 / Megatron initialisation does, so that
    a layer adds to the stream and does not replace it. The router's
    correction bias is N(0, 0.05^2), norms are 1."""
    h, f, i = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    ld, lm, g = cfg.first_k_dense_replace, cfg.num_moe_layers, cfg.n_groups
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    back = 1.0 / math.sqrt(2 * cfg.num_hidden_layers)

    def mk(shape, fan_in, gain=1.0):
        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        if len(shape) < 3:
            return draw(next(keys), shape)
        # a stacked array a layer at a time: the float32 draw of all
        # the experts (11 GB at 7 x 130 x 2048 x 1536) never exists
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(next(keys), shape[0]))

    def attn(n):
        out = {name: {"w": mk((n,) + s, s[1],
                              back if name == "o_proj" else 1.0)}
               for name, s in linear_shapes(cfg).items()}
        out["kv_a_layernorm"] = jnp.ones((n, cfg.kv_lora_rank), dtype)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            out[norm] = jnp.ones((n, h), dtype)
        return out

    dense = attn(ld)
    dense["gate_up_proj"] = {"w": mk((ld, 2 * f, h), h)}
    dense["down_proj"] = {"w": mk((ld, h, f), f, back)}
    layers = attn(lm)
    layers["router"] = {
        "w": mk((lm, cfg.n_routed_experts, h), h),
        "bias": 0.05 * jax.random.normal(
            next(keys), (lm, cfg.n_routed_experts), jnp.float32)}
    return {
        "embed_tokens": mk((cfg.vocab_size, h), 1.0),
        "norm": jnp.ones((h,), dtype),
        "lm_head": {"w": mk((cfg.vocab_size, h), h)},
        "dense_layers": dense,
        "layers": layers,
        "experts": {"w_gate_up": mk((lm, g, h, 2 * i), h),
                    "w_down": mk((lm, g, i, h), i, back)},
    }


# ---------------------------------------------------------------------------
# layer math
# ---------------------------------------------------------------------------

def _einsum32(eq: str, a, b):
    """A product with a float32 result. bfloat16 operands go to the MXU
    as they are; the CPU backend has no bf16 x bf16 -> f32 dot, so off
    the TPU they are widened first (the same sums, exactly)."""
    if jax.default_backend() != "tpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def rope_interleaved(x, positions, theta: float):
    """RoPE on a head whose pairs are stored ``(2i, 2i+1)``: de-
    interleave to ``(i, i + d/2)``, then rotate by halves. x (B, T, H,
    D). The result stays de-interleaved; q and k are treated alike, so
    their products are those of the pairwise rotation."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rope(x, positions, theta)


def mla_project(lp, h, positions, cfg: DeepseekConfig):
    """``q_nope`` (B, T, nh, nope), rotated ``q_rope`` (B, T, nh, rope),
    and the cached row's two parts: normed ``c`` (B, T, lora), rotated
    ``k_r`` (B, T, rope)."""
    b, t, _ = h.shape
    nh = cfg.num_attention_heads
    q = _linear(lp["q_proj"], h).reshape(b, t, nh, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    ckr = _linear(lp["kv_a_proj"], h)
    c, k_r = jnp.split(ckr, [cfg.kv_lora_rank], axis=-1)
    c = rms_norm(c, lp["kv_a_layernorm"], cfg.rms_norm_eps)
    rot = rope_interleaved if cfg.rope_interleave else rope
    q_rope = rot(q_rope, positions, cfg.rope_theta)
    k_r = rot(k_r[:, :, None], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c, k_r


def _kv_b(lp, cfg: DeepseekConfig):
    """``W_uk``, ``W_uv``: (nh, nope | v, lora) halves of ``kv_b_proj``."""
    w = lp["kv_b_proj"]["w"].reshape(
        cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim,
        cfg.kv_lora_rank)
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


def latent_row(c, k_r, cfg: DeepseekConfig, dtype):
    """``[c | k_r | 0]`` at the pool's width and dtype."""
    pad = cfg.latent_width - cfg.latent_dim
    row = jnp.concatenate([c, k_r], axis=-1).astype(dtype)
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def absorbed_query(lp, q_nope, q_rope, cfg: DeepseekConfig):
    """The query in the cache's coordinates: ``[q_nope W_uk^T | q_rope
    | 0]`` (..., nh, latent_width), float32."""
    w_uk, _ = _kv_b(lp, cfg)
    q_lat = _einsum32("...hd,hdc->...hc", q_nope, w_uk)
    pad = cfg.latent_width - cfg.latent_dim
    q = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)
    return jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])


def absorbed_output(lp, o_lat, cfg: DeepseekConfig, dtype):
    """``o_lat`` (..., nh, lora) -> ``o_lat W_uv`` (..., nh * v)."""
    _, w_uv = _kv_b(lp, cfg)
    o = _einsum32("...hc,hvc->...hv", o_lat.astype(dtype), w_uv)
    return o.reshape(o.shape[:-2] + (-1,)).astype(dtype)


def mla_attend_absorbed(lp, q_nope, q_rope, rows, q_positions, valid,
                        cfg: DeepseekConfig, dtype):
    """Absorbed-form attention over cached rows. ``rows`` (B, S, W)
    ``[c | k_r | 0]``; ``valid`` (B, S); position ``s`` is seen by a
    query at ``q_positions`` (B, T) >= s. Returns (B, T, nh * v)."""
    q = absorbed_query(lp, q_nope, q_rope, cfg)
    kv = rows.astype(jnp.float32)
    s = jnp.einsum("bthw,bsw->bhts", q, kv) * cfg.attn_scale
    seen = (jnp.arange(rows.shape[1])[None, None, :]
            <= q_positions[..., None]) & valid[:, None, :]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    o_lat = jnp.einsum("bhts,bsc->bthc", p, kv[..., :cfg.kv_lora_rank])
    return absorbed_output(lp, o_lat, cfg, dtype)


def mla_attend_expanded(lp, q_nope, q_rope, c, k_r, length,
                        cfg: DeepseekConfig, dtype):
    """Expanded-form causal attention of a chunk over itself (one
    sequence from position 0): K and V per head from the chunk's own
    ``c`` (T, lora) / ``k_r`` (T, rope), a block of queries at a time so
    that no (nh, T, T) score array exists. ``length`` masks the bucket's
    padding. q_* (T, nh, ·). Returns (T, nh * v)."""
    t = c.shape[0]
    w_uk, w_uv = _kv_b(lp, cfg)
    k_nope = jnp.einsum("sc,hdc->shd", c, w_uk)
    v = jnp.einsum("sc,hvc->shv", c, w_uv)
    qb = min(t, ATTN_QUERY_BLOCK)
    nq = t // qb
    key_pos = jnp.arange(t)

    def block(args):
        qn, qr, pos = args                                  # (qb, nh, ·)
        s = (_einsum32("qhd,shd->hqs", qn, k_nope)
             + _einsum32("qhr,sr->hqs", qr, k_r)) * cfg.attn_scale
        seen = (key_pos[None, :] <= pos[:, None]) \
            & (key_pos[None, :] < length)
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return _einsum32("hqs,shv->qhv", p.astype(dtype), v)

    out = jax.lax.map(block, (
        q_nope.reshape(nq, qb, *q_nope.shape[1:]),
        q_rope.reshape(nq, qb, *q_rope.shape[1:]),
        key_pos.reshape(nq, qb)))
    return out.reshape(t, -1).astype(dtype)


def route(router, h, cfg: DeepseekConfig):
    """(T, H) -> chosen experts (T, k) int32 and their weights (T, k)
    float32: :func:`kernels.moe.route_sigmoid` at this configuration's
    ``k``, normalisation and scaling factor."""
    return moe.route_sigmoid(router, h, cfg.num_experts_per_tok,
                             cfg.norm_topk_prob, cfg.routed_scaling_factor)


def moe_stats(group_sizes, cfg: DeepseekConfig):
    """(3,) int32 of one expert layer: routed assignments computed,
    routed experts with a token, the fullest expert's tokens."""
    routed = group_sizes[:cfg.n_routed_experts]
    return jnp.stack([routed.sum(), (routed > 0).sum(), routed.max()]) \
        .astype(jnp.int32)


def expert_layer(lp, experts, layer, h, live, cfg: DeepseekConfig):
    """Routed + shared experts for ``h`` (T, H); ``experts`` the whole
    stack viewed ``(Lm·G, …)``; ``live`` (T,) rows that count. Returns
    ``(y (T, H) in h's dtype, stats (3,), chosen experts (T, k))``."""
    idx, w = route(lp["router"], h, cfg)
    t, s = h.shape[0], cfg.n_shared_experts
    shared = cfg.n_routed_experts + jnp.arange(s, dtype=jnp.int32)
    groups = jnp.concatenate(
        [idx, jnp.broadcast_to(shared, (t, s))], axis=1)
    weights = jnp.concatenate([w, jnp.ones((t, s), jnp.float32)], axis=1)
    y, sizes = moe.grouped_ffn(h, groups, weights, live,
                               experts["w_gate_up"], experts["w_down"],
                               layer, cfg.n_groups)
    return y.astype(h.dtype), moe_stats(sizes, cfg), idx


def _flat_experts(params):
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["experts"])


def _decoder(params, cfg: DeepseekConfig, x, attend, live):
    """The layers over the stream ``x`` (B, T, H). ``attend(l, lp, h)``
    -> ``(attn (B, T, nh * v), row)`` does one layer's attention and
    hands back what it wants collected per layer. Returns ``(x, rows
    (L, …), stats (3,), chosen (Lm, B·T, k))``: the dense layers
    unrolled, the expert layers in a rolled scan with the expert stack
    closed over."""
    b, t, hid = x.shape
    ld = cfg.first_k_dense_replace
    experts = _flat_experts(params)
    eps = cfg.rms_norm_eps

    def block(x, lp, l, ffn):
        h = rms_norm(x, lp["input_layernorm"], eps)
        attn, row = attend(l, lp, h)
        x = x + _linear(lp["o_proj"], attn)
        h2 = rms_norm(x, lp["post_attention_layernorm"], eps)
        y, *aux = ffn(lp, l, h2)
        return x + y, row, aux

    rows = []
    for l in range(ld):
        lp = jax.tree_util.tree_map(lambda a: a[l], params["dense_layers"])
        x, row, _ = block(x, lp, l, lambda lp, l, h2: (
            mlp(lp, h2, x.dtype),))
        rows.append(row)

    def moe_ffn(lp, l, h2):
        y, stats, idx = expert_layer(
            lp, experts, l - ld, h2.reshape(-1, hid), live.reshape(-1), cfg)
        return y.reshape(b, t, hid), stats, idx

    def step(carry, inputs):
        x, total = carry
        lp, l = inputs
        x, row, (stats, idx) = block(x, lp, l, moe_ffn)
        # touched and the fullest load add up over layers; the host
        # divides by layer-steps
        return (x, total + stats), (row, idx)

    (x, stats), (moe_rows, chosen) = jax.lax.scan(
        step, (x, jnp.zeros(3, jnp.int32)),
        (params["layers"], ld + jnp.arange(cfg.num_moe_layers)))
    rows = jnp.concatenate([jnp.stack(rows), moe_rows]) if rows \
        else moe_rows
    return rms_norm(x, params["norm"], eps), rows, stats, chosen


# ---------------------------------------------------------------------------
# dense-cache forward (generate(), the tolerance floor's bf16 side)
# ---------------------------------------------------------------------------

def init_cache(cfg: DeepseekConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    return {"kv": jnp.zeros((cfg.num_hidden_layers, batch, max_len,
                             cfg.latent_width), dtype),
            "pos": jnp.zeros((), jnp.int32)}


def forward(params: Dict[str, Any], cfg: DeepseekConfig,
            tokens: jnp.ndarray, cache: Dict[str, jnp.ndarray],
            positions: jnp.ndarray, routes: bool = False
            ) -> Tuple[jnp.ndarray, Dict]:
    """(B, T) tokens at ``positions`` over a contiguous latent cache:
    logits (B, T, V) float32 and the cache with the new rows; with
    ``routes`` also the experts every token chose, (Lm, B·T, k)."""
    x = params["embed_tokens"][tokens]
    start = cache["pos"]
    t = tokens.shape[1]
    valid = jnp.arange(cache["kv"].shape[2])[None, :] < start + t
    live = jnp.ones(tokens.shape, bool)

    def attend(l, lp, h):
        q_nope, q_rope, c, k_r = mla_project(lp, h, positions, cfg)
        rows = jax.lax.dynamic_update_slice(
            cache["kv"][l], latent_row(c, k_r, cfg, cache["kv"].dtype),
            (0, start, 0))
        return mla_attend_absorbed(lp, q_nope, q_rope, rows, positions,
                                   valid, cfg, x.dtype), rows

    x, rows, _, chosen = _decoder(params, cfg, x, attend, live)
    logits = _linear(params["lm_head"], x)
    cache = {"kv": rows, "pos": start + t}
    if routes:
        return logits.astype(jnp.float32), cache, chosen
    return logits.astype(jnp.float32), cache


# ---------------------------------------------------------------------------
# the paged engine's entry points
# ---------------------------------------------------------------------------

def page_classes(cfg: DeepseekConfig):
    """The engine's cache for this family: ONE class of latent rows,
    every layer, every token, and no V pool (``LLMServer`` asks the
    family; docs/KVCACHE.md)."""
    from bigdl_tpu.llm.kvcache.classes import PageClass
    return [PageClass("latent", cfg.num_hidden_layers, 1,
                      cfg.latent_width, None)]


# the decode step's stats vector, appended to the fetched token vector:
# summed over the step's expert layers (kernels.sampling)
STEP_STATS = ("moe_assignments_total", "moe_experts_touched_total",
              "moe_max_load_total")


def host_step_stats(cfg: DeepseekConfig, ctx_lens) -> Dict[str, int]:
    """What the host knows of a decode step it dispatches: the expert
    layers it runs and the cached tokens its live rows attend."""
    return {"moe_layer_steps_total": cfg.num_moe_layers,
            "moe_token_layers_total": len(ctx_lens) * cfg.num_moe_layers,
            "latent_ctx_tokens_total": int(ctx_lens.sum())}


def paged_decode_step(params, cfg: DeepseekConfig, kv_pages, _none, bt,
                      lens, toks, *, page: int):
    """One decode step over the latent pool: as
    ``llama.paged_decode_step`` (pool read-only inside the layers,
    the current token folded in by the flash combine, one in-place
    write after them), with absorbed-form attention. Rows with ``lens
    == 0`` are the sampled step's masked lanes: they route to no expert
    and read no expert's weights. Returns ``(logits (B, V) f32,
    kv_pages, None, stats (3,))``."""
    from bigdl_tpu.llm.kernels.paged_attention import (
        latent_attention_stats, merge_attention_partial)
    from bigdl_tpu.llm.kvcache.write import write_kv
    b = toks.shape[0]
    num_pages = kv_pages.shape[1]
    flat = kv_pages.reshape((-1,) + kv_pages.shape[2:])
    x = params["embed_tokens"][toks][:, None]
    positions = lens[:, None].astype(jnp.int32)
    lora = cfg.kv_lora_rank

    def attend(l, lp, h):
        q_nope, q_rope, c, k_r = mla_project(lp, h, positions, cfg)
        q = absorbed_query(lp, q_nope[:, 0], q_rope[:, 0], cfg)
        row = latent_row(c[:, 0], k_r[:, 0], cfg, kv_pages.dtype)
        acc, m, lsum = latent_attention_stats(
            q, flat, bt + l * num_pages, lens, page_size=page, dv=lora,
            scale=cfg.attn_scale)
        o_lat = merge_attention_partial(
            acc, m, lsum, q, row[:, None], row[:, None, :lora],
            scale=cfg.attn_scale)
        return absorbed_output(lp, o_lat, cfg, x.dtype)[:, None], row

    x, rows, stats, _ = _decoder(params, cfg, x, attend,
                                 (lens > 0)[:, None])
    logits = _linear(params["lm_head"], x)
    phys = bt[jnp.arange(b), lens // page]
    kv_pages = write_kv(kv_pages, phys, lens % page, rows[:, :, None])
    return logits[:, 0].astype(jnp.float32), kv_pages, None, stats


from bigdl_tpu.llm.kernels.sampling import make_sampled_step  # noqa: E402

paged_decode_step_sampled = make_sampled_step(paged_decode_step)


def paged_prefill_ragged(params, cfg: DeepseekConfig, kv_pages, _none,
                         toks, length, offset, bt_row, phys, slots,
                         fork_dst, fork_src, *, page: int):
    """Prefill of one whole prompt (``offset`` 0: the features that
    would resume from cached pages refuse this family) in the engine's
    ragged-prefill shape: expanded-form attention of the bucket over
    itself, then the latent rows written page by page. Returns
    ``(kv_pages, None, last_logits (V,) f32)``."""
    from bigdl_tpu.llm.kvcache.write import write_kv_run
    bucket = toks.shape[1]
    positions = (offset + jnp.arange(bucket, dtype=jnp.int32))[None]
    x = params["embed_tokens"][toks]
    live = positions < offset + length

    def attend(l, lp, h):
        q_nope, q_rope, c, k_r = mla_project(lp, h, positions, cfg)
        # attend at pool precision, as a later decode step will read it
        row = latent_row(c[0], k_r[0], cfg, kv_pages.dtype)
        attn = mla_attend_expanded(
            lp, q_nope[0], q_rope[0], row[:, :cfg.kv_lora_rank],
            row[:, cfg.kv_lora_rank:cfg.latent_dim], length, cfg, x.dtype)
        return attn[None], row

    x, rows, _, _ = _decoder(params, cfg, x, attend, live)
    last = jax.lax.dynamic_index_in_dim(x[0], length - 1, 0, keepdims=True)
    logits = _linear(params["lm_head"], last)
    kv_pages = write_kv_run(kv_pages, phys, slots, rows[:, :, None])
    return kv_pages, None, logits[0].astype(jnp.float32)


class DeepseekForCausalLM(CausalLMFacade):
    """Generation facade — shared driver (see models._facade)."""

    _forward = staticmethod(forward)
    _init_cache = staticmethod(init_cache)
    _init_params = staticmethod(init_params)

    @staticmethod
    def _quantize_params(params, qtype):
        raise NotImplementedError(
            "expert-stacked weights are not ggml-quantized yet; the "
            "deepseek family runs bf16")
