"""MiMo-V2 decoders (``model_type`` ``mimo_v2``; the configuration the
chip runs is MiMo-V2.5's language model, ISSUE 31): full-attention and
sliding-window layers mixed in one model, K and V of different widths,
a learned sink on the window layers, sigmoid-routed experts of which a
chip holds its share.

Per layer, pre-norm residual, RMSNorm. By ``hybrid_layer_pattern[l]``:

- **0, full attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads; every position sees every earlier
  one; RoPE with ``rope_theta``.
- **1, sliding window (SWA)**: the same query heads over
  ``swa_num_key_value_heads`` KV heads; position ``t`` sees ``t -
  sliding_window + 1 .. t``; RoPE with ``swa_rope_theta``; one learned
  scalar a query head (the **sink**) joins the softmax's denominator
  and carries no value.

Both: one fused projection ``h -> [q | k | v]`` (heads of ``head_dim``,
``head_dim``, ``v_head_dim``), RoPE by halves over the first
``int(partial_rotary_factor * head_dim)`` numbers of every q and k head
(the rest pass through), the values times ``attention_value_scale``
right after their projection (what is cached is the scaled value),
scores ``q.k * head_dim ** -0.5``, output ``heads * v_head_dim -> h``.

Feed-forward by ``moe_layer_freq[l]``: 0 a dense SwiGLU of
``intermediate_size``; 1 ``n_routed_experts`` SwiGLUs of
``moe_intermediate_size``, ``num_experts_per_tok`` a token, chosen and
weighed by :func:`kernels.moe.route_sigmoid` (scaling 1, no shared
expert). **An expert-parallel share**: the chip holds the experts
``first_expert .. first_expert + experts_held - 1`` of every expert
layer; the router keeps its published width, an assignment to an expert
held elsewhere is left out, and the partial sum of the held experts is
what goes on to the next layer (``kernels.moe.grouped_ffn(held=...)``;
no code stands in for the other chips or their exchange).

Parameters (:func:`init_params`): ``layers`` is a list with one dict a
layer (the two kinds of layer have attention weights of two shapes, so
nothing is stacked and nothing is sliced: the layers are unrolled);
``experts`` of an expert layer are ``w_gate_up`` (held, H, 2I) and
``w_down`` (held, I, H) in x @ W layout, gate columns then up columns.

The paged engine caches in **two page classes** (:func:`page_classes`):
``full`` keeps every token of the full layers, ``window`` a ring of the
window layers' last positions, each ONE pool of rows ``[k | 0 | v]`` a
KV head: the key held ``k_width`` = 256 wide (192 numbers, then zeros:
the lanes a page DMA needs, and what a 192-wide minor dimension
occupies in HBM anyway), the value in the 128 columns after it, so
that a page's keys and values come in one DMA. There is no V pool.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.llm.kernels import moe
from bigdl_tpu.llm.kernels.paged_attention import LANE
from bigdl_tpu.llm.models._facade import CausalLMFacade
from bigdl_tpu.llm.models.llama import _linear, mlp, rms_norm, rope

# queries of a dense forward attended at a time
ATTN_QUERY_BLOCK = 256


@dataclasses.dataclass
class MimoConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # 0 full attention | 1 sliding window, a layer
    hybrid_layer_pattern: Tuple[int, ...] = \
        (0, 1, 1, 1, 1, 0) + 7 * (1, 1, 1, 1, 1, 0)
    # 0 dense FFN | 1 experts, a layer
    moe_layer_freq: Tuple[int, ...] = (0,) + 47 * (1,)
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the chip's share of every expert layer
    first_expert: int = 0
    experts_held: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    # tokens of a prompt one pass of the layers takes (the engine's
    # prefill program loops over a longer prompt's chunks itself)
    prefill_chunk: int = 1024

    def __post_init__(self):
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(self.moe_layer_freq)
        n = self.num_hidden_layers
        if len(self.hybrid_layer_pattern) != n \
                or len(self.moe_layer_freq) != n:
            raise ValueError(
                f"hybrid_layer_pattern ({len(self.hybrid_layer_pattern)}) "
                f"and moe_layer_freq ({len(self.moe_layer_freq)}) must "
                f"name all {n} layers")
        if not 0 <= self.first_expert <= self.first_expert \
                + self.experts_held <= self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert} .. +{self.experts_held} "
                f"are not among {self.n_routed_experts}")

    @property
    def rotary_dim(self) -> int:
        return int(self.partial_rotary_factor * self.head_dim)

    @property
    def attn_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def k_width(self) -> int:
        """The K pools' minor dimension: ``head_dim`` lane-padded."""
        return -(-self.head_dim // LANE) * LANE

    @property
    def v_width(self) -> int:
        return -(-self.v_head_dim // LANE) * LANE

    @property
    def row_width(self) -> int:
        """A cached row: the key at ``k_width``, then the value."""
        return self.k_width + self.v_width

    def kv_heads(self, kind: int) -> int:
        return self.swa_num_key_value_heads if kind \
            else self.num_key_value_heads

    def theta(self, kind: int) -> float:
        return self.swa_rope_theta if kind else self.rope_theta

    def has_sink(self, kind: int) -> bool:
        return self.add_swa_attention_sink_bias if kind \
            else self.add_full_attention_sink_bias

    def layers_of(self, kind: int) -> List[int]:
        return [l for l, k in enumerate(self.hybrid_layer_pattern)
                if k == kind]

    @property
    def num_moe_layers(self) -> int:
        return sum(self.moe_layer_freq)

    @classmethod
    def tiny(cls, vocab: int = 256, **over) -> "MimoConfig":
        """Tiny widths that keep the published asymmetries: K wider
        than V, twice the KV heads on window layers, a window shorter
        than a test prompt, a quarter of the experts held."""
        keys = dict(
            vocab_size=vocab, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=2,
            swa_num_key_value_heads=4, head_dim=24, v_head_dim=16,
            sliding_window=16, hybrid_layer_pattern=(0, 1, 1, 0),
            moe_layer_freq=(0, 1, 1, 1), n_routed_experts=16,
            num_experts_per_tok=4, first_expert=4, experts_held=4,
            max_position_embeddings=2048, prefill_chunk=32)
        keys.update(over)
        return cls(**keys)

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "MimoConfig":
        """From the keys of a ``mimo_v2`` ``config.json`` (text model).
        ``n_routed_experts`` may be the chip's share, with the published
        count under ``published`` and the share's start under
        ``first_expert``. What the equations above do not cover is
        refused by name."""
        g = hf.get
        rs = g("rope_scaling") or {}
        unsupported = {
            "rope_scaling": (rs.get("rope_type") or rs.get("type")
                             or "default") != "default",
            "n_group/topk_group != 1":
                (g("n_group", 1), g("topk_group", 1)) != (1, 1),
            "scoring_func != sigmoid":
                g("scoring_func", "sigmoid") != "sigmoid",
            "n_shared_experts": bool(g("n_shared_experts")),
            "attention_bias": bool(g("attention_bias", False)),
            "tie_word_embeddings": bool(g("tie_word_embeddings", False)),
            "hidden_act != silu": g("hidden_act", "silu") != "silu",
            "swa heads or widths differ from the full layers'":
                (g("swa_num_attention_heads", g("num_attention_heads")),
                 g("swa_head_dim", g("head_dim")),
                 g("swa_v_head_dim", g("v_head_dim")))
                != (g("num_attention_heads"), g("head_dim"),
                    g("v_head_dim")),
            "sliding_window != sliding_window_size":
                g("sliding_window_size", g("sliding_window"))
                != g("sliding_window"),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"mimo_v2 config uses {bad}, which this family does not "
                "implement")
        names = {f.name for f in dataclasses.fields(cls)}
        keys = {k: v for k, v in hf.items() if k in names and v is not None}
        keys["rms_norm_eps"] = float(g("rms_norm_eps",
                                       g("layernorm_epsilon", 1e-5)))
        held = int(g("n_routed_experts"))
        keys["n_routed_experts"] = int(
            (g("published") or {}).get("n_routed_experts", held))
        keys["experts_held"] = int(g("experts_held", held))
        keys["first_expert"] = int(g("first_expert", 0))
        keys["routed_scaling_factor"] = float(
            g("routed_scaling_factor") or 1.0)
        for k in ("rope_theta", "swa_rope_theta"):
            keys[k] = float(keys[k])
        return cls(**keys)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def qkv_widths(cfg: MimoConfig, kind: int) -> Tuple[int, int, int]:
    """Columns of the fused projection: all q heads, the kind's k
    heads, its v heads."""
    hkv = cfg.kv_heads(kind)
    return (cfg.num_attention_heads * cfg.head_dim, hkv * cfg.head_dim,
            hkv * cfg.v_head_dim)


def init_params(cfg: MimoConfig, seed: int = 0, dtype=jnp.bfloat16,
                back: float = None) -> Dict[str, Any]:
    """Seeded parameters, drawn where JAX's default device is: every
    linear zero-mean at unit gain, the projections back into the stream
    (``o_proj``, ``down_proj``, the experts' ``w_down``) at ``back``
    (default ``1 / sqrt(2 L)``); sink scalars N(0, 1), the router's
    correction bias N(0, 0.05^2), both float32; norms 1."""
    h, i = cfg.hidden_size, cfg.moe_intermediate_size
    if back is None:
        back = 1.0 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 8 * cfg.num_hidden_layers + 8))

    def mk(shape, fan_in, gain=1.0):
        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        if len(shape) < 3:
            return draw(next(keys), shape)
        # an expert at a time: the float32 draw of a layer's experts
        # (1.6 GB at 16 x 4096 x 4096) never exists
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(next(keys), shape[0]))

    layers = []
    for kind, sparse in zip(cfg.hybrid_layer_pattern, cfg.moe_layer_freq):
        lp = {"qkv_proj": {"w": mk((sum(qkv_widths(cfg, kind)), h), h)},
              "o_proj": {"w": mk(
                  (h, cfg.num_attention_heads * cfg.v_head_dim),
                  cfg.num_attention_heads * cfg.v_head_dim, back)},
              "input_layernorm": jnp.ones((h,), dtype),
              "post_attention_layernorm": jnp.ones((h,), dtype)}
        if cfg.has_sink(kind):
            lp["sink"] = jax.random.normal(
                next(keys), (cfg.num_attention_heads,), jnp.float32)
        if sparse:
            lp["router"] = {
                "w": mk((cfg.n_routed_experts, h), h),
                "bias": 0.05 * jax.random.normal(
                    next(keys), (cfg.n_routed_experts,), jnp.float32)}
            lp["experts"] = {
                "w_gate_up": mk((cfg.experts_held, h, 2 * i), h),
                "w_down": mk((cfg.experts_held, i, h), i, back)}
        else:
            f = cfg.intermediate_size
            lp["gate_up_proj"] = {"w": mk((2 * f, h), h)}
            lp["down_proj"] = {"w": mk((h, f), f, back)}
        layers.append(lp)
    return {"embed_tokens": mk((cfg.vocab_size, h), 1.0),
            "norm": jnp.ones((h,), dtype),
            "lm_head": {"w": mk((cfg.vocab_size, h), h)},
            "layers": layers}


# ---------------------------------------------------------------------------
# layer math
# ---------------------------------------------------------------------------

def project_qkv(lp, h, positions, cfg: MimoConfig, kind: int):
    """h (B, T, H) -> rotated q (B, T, nh, D), rotated k (B, T, hkv,
    D), scaled v (B, T, hkv, Dv)."""
    b, t, _ = h.shape
    nq, nk, _ = qkv_widths(cfg, kind)
    qkv = _linear(lp["qkv_proj"], h)
    q = qkv[..., :nq].reshape(b, t, -1, cfg.head_dim)
    k = qkv[..., nq:nq + nk].reshape(b, t, -1, cfg.head_dim)
    v = qkv[..., nq + nk:].reshape(b, t, -1, cfg.v_head_dim)
    rot, theta = cfg.rotary_dim, cfg.theta(kind)

    def rotate(x):
        return jnp.concatenate(
            [rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)
    v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(v.dtype)
    return rotate(q), rotate(k), v


def _lane_pad(x, width: int):
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def cached_row(k, v, cfg: MimoConfig, dtype):
    """``[k | 0 | v | 0]`` at the pools' width and dtype."""
    return jnp.concatenate([_lane_pad(k, cfg.k_width),
                            _lane_pad(v, cfg.v_width)], -1).astype(dtype)


def route(router, h, cfg: MimoConfig):
    """The family's router: :func:`kernels.moe.route_sigmoid` over all
    ``n_routed_experts``, whatever the chip holds."""
    return moe.route_sigmoid(router, h, cfg.num_experts_per_tok,
                             cfg.norm_topk_prob, cfg.routed_scaling_factor)


def expert_layer(lp, h, live, cfg: MimoConfig):
    """The held experts' part of the routed sum for ``h`` (T, H);
    ``live`` (T,) the rows that count. Returns ``(y (T, H) in h's
    dtype, stats (4,) int32: assignments computed here, assignments
    left to the chips that hold the other experts, held experts with a
    token, the fullest held expert's tokens; chosen experts (T, k))``."""
    idx, w = route(lp["router"], h, cfg)
    y, sizes = moe.grouped_ffn(
        h, idx, w, live, lp["experts"]["w_gate_up"],
        lp["experts"]["w_down"], 0, cfg.experts_held,
        held=(cfg.first_expert, cfg.experts_held))
    return y.astype(h.dtype), moe.share_stats(sizes, live, idx.shape[1]), idx


def _decoder(params, cfg: MimoConfig, x, attend, live):
    """The unrolled layers over the stream ``x`` (B, T, H).
    ``attend(l, kind, lp, h)`` -> ``(attn (B, T, nh * Dv), what it
    wants kept of the layer)``. Returns ``(x after the final norm, the
    kept values by layer, stats (4,), chosen experts a expert layer)``."""
    b, t, hid = x.shape
    eps = cfg.rms_norm_eps
    kept, chosen = [], []
    stats = jnp.zeros(4, jnp.int32)
    for l, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["input_layernorm"], eps)
        attn, keep = attend(l, cfg.hybrid_layer_pattern[l], lp, h)
        kept.append(keep)
        x = x + _linear(lp["o_proj"], attn)
        h2 = rms_norm(x, lp["post_attention_layernorm"], eps)
        if cfg.moe_layer_freq[l]:
            y, s, idx = expert_layer(lp, h2.reshape(-1, hid),
                                     live.reshape(-1), cfg)
            x = x + y.reshape(b, t, hid)
            stats = stats + s
            chosen.append(idx)
        else:
            x = x + mlp(lp, h2, x.dtype)
    return rms_norm(x, params["norm"], eps), kept, stats, chosen


# ---------------------------------------------------------------------------
# dense-cache forward (generate(), the tolerance floor's bf16 side)
# ---------------------------------------------------------------------------

def init_cache(cfg: MimoConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """One contiguous K and V a layer, every position (the window
    layers mask; only the paged engine bounds what they keep)."""
    def kv(kind):
        hkv = cfg.kv_heads(kind)
        return (jnp.zeros((batch, max_len, hkv, cfg.head_dim), dtype),
                jnp.zeros((batch, max_len, hkv, cfg.v_head_dim), dtype))
    return {"kv": [kv(kind) for kind in cfg.hybrid_layer_pattern],
            "pos": jnp.zeros((), jnp.int32)}


def attend_dense(q, k_all, v_all, q_positions, n_valid, cfg: MimoConfig,
                 kind: int, sink):
    """Plain masked attention, a block of queries at a time. q (B, T,
    nh, D) at ``q_positions`` (B, T); k_all/v_all (B, S, hkv, ·) hold
    positions ``0 .. n_valid - 1``. The sink is one more softmax
    column. Returns (B, T, nh * Dv) in q's dtype."""
    b, t, nh, d = q.shape
    hkv = k_all.shape[2]
    g = nh // hkv
    kf, vf = k_all.astype(jnp.float32), v_all.astype(jnp.float32)
    key_pos = jnp.arange(k_all.shape[1])
    qb = min(t, ATTN_QUERY_BLOCK)
    while t % qb:
        qb -= 1

    def block(args):
        qq, pos = args                              # (B, qb, nh, D), (B, qb)
        qg = qq.reshape(b, qb, hkv, g, d).astype(jnp.float32)
        s = jnp.einsum("bthgd,bshd->bhgts", qg, kf) * cfg.attn_scale
        seen = (key_pos[None, None, :] <= pos[..., None]) \
            & (key_pos[None, None, :] < n_valid)
        if kind:
            seen &= key_pos[None, None, :] > pos[..., None] \
                - cfg.sliding_window
        s = jnp.where(seen[:, None, None], s, -1e30)
        if sink is not None:
            col = jnp.broadcast_to(
                sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1),
                s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgts,bshd->bthgd", p, vf)
        return o.reshape(b, qb, -1)

    nq = t // qb
    out = jax.lax.map(block, (
        q.reshape(b, nq, qb, nh, d).transpose(1, 0, 2, 3, 4),
        q_positions.reshape(b, nq, qb).transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(b, t, -1).astype(q.dtype)


def forward(params: Dict[str, Any], cfg: MimoConfig, tokens: jnp.ndarray,
            cache: Dict[str, Any], positions: jnp.ndarray,
            routes: bool = False):
    """(B, T) tokens at ``positions`` over contiguous caches: logits
    (B, T, V) float32 and the caches with the new rows; with ``routes``
    also the experts every token chose, a list of (B·T, k) an expert
    layer."""
    x = params["embed_tokens"][tokens]
    start = cache["pos"]
    t = tokens.shape[1]
    live = jnp.ones(tokens.shape, bool)

    def attend(l, kind, lp, h):
        q, k, v = project_qkv(lp, h, positions, cfg, kind)
        k_all, v_all = cache["kv"][l]
        k_all = jax.lax.dynamic_update_slice(
            k_all, k.astype(k_all.dtype), (0, start, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            v_all, v.astype(v_all.dtype), (0, start, 0, 0))
        return attend_dense(q, k_all, v_all, positions, start + t, cfg,
                            kind, lp.get("sink")), (k_all, v_all)

    x, kv, _, chosen = _decoder(params, cfg, x, attend, live)
    logits = _linear(params["lm_head"], x).astype(jnp.float32)
    cache = {"kv": kv, "pos": start + t}
    if routes:
        return logits, cache, chosen
    return logits, cache


# ---------------------------------------------------------------------------
# the paged engine's entry points
# ---------------------------------------------------------------------------

def page_classes(cfg: MimoConfig):
    """Two kinds of cache side by side (docs/KVCACHE.md): ``full``, the
    full-attention layers' rows for every token; ``window``, a ring of
    the window layers' last ``sliding_window`` positions. Each one pool
    of ``[key | value]`` rows and no V pool."""
    from bigdl_tpu.llm.kvcache.classes import PageClass
    return [PageClass("full", len(cfg.layers_of(0)),
                      cfg.num_key_value_heads, cfg.row_width, None),
            PageClass("window", len(cfg.layers_of(1)),
                      cfg.swa_num_key_value_heads, cfg.row_width, None,
                      keeps=cfg.sliding_window)]


# the decode step's stats vector, appended to the fetched token vector:
# summed over the step's expert layers (kernels.sampling)
STEP_STATS = ("moe_assignments_total", "moe_assignments_elsewhere_total",
              "moe_experts_touched_total", "moe_max_load_total")


def host_step_stats(cfg: MimoConfig, ctx_lens) -> Dict[str, int]:
    """What the host knows of a decode step it dispatches: the expert
    layers it runs, and the cached tokens its live rows attend in the
    class that keeps them all and in the one that keeps a window."""
    import numpy as np
    return {"moe_layer_steps_total": cfg.num_moe_layers,
            "moe_token_layers_total": len(ctx_lens) * cfg.num_moe_layers,
            "full_ctx_tokens_total": int(ctx_lens.sum()),
            "window_ctx_tokens_total": int(
                np.minimum(ctx_lens, cfg.sliding_window).sum())}


def _flat(pool):
    """(L, P, …) -> (L·P, …): a layer is addressed by offsetting its
    table, never by slicing its pool."""
    return pool.reshape((-1,) + pool.shape[2:])


def _class_index(cfg: MimoConfig):
    """layer -> its index among the layers of its kind."""
    seen = [0, 0]
    out = []
    for kind in cfg.hybrid_layer_pattern:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def _stack_by_kind(cfg: MimoConfig, kept):
    """Per-layer rows -> by class ``(Lc, T, hkv, row_width)``."""
    return [jnp.stack([kept[l] for l in cfg.layers_of(kind)])
            for kind in (0, 1)]


def paged_decode_step(params, cfg: MimoConfig, kv_pages, _none, bt, lens,
                      toks, *, page: int):
    """One decode step over both page classes: as
    ``llama.paged_decode_step`` (pools read-only inside the layers, the
    current token folded in by the flash combine, one in-place write a
    pool after them). ``kv_pages`` and ``bt`` are pairs, the full class
    then the window class, whose table is a ring; there are no V pools
    (``_none`` is a pair of None). Rows with ``lens == 0`` are the
    sampled step's masked lanes: they route to no expert. Returns
    ``(logits (B, V) f32, kv_pages, _none, stats (4,))``."""
    from bigdl_tpu.llm.kernels.hybrid_attention import \
        attention_decode_stats
    from bigdl_tpu.llm.kernels.paged_attention import \
        merge_attention_partial
    from bigdl_tpu.llm.kvcache.write import write_kv
    b = toks.shape[0]
    flat = [_flat(p) for p in kv_pages]
    within = _class_index(cfg)
    x = params["embed_tokens"][toks][:, None]
    positions = lens[:, None].astype(jnp.int32)
    kw = cfg.k_width

    def attend(l, kind, lp, h):
        q, k, v = project_qkv(lp, h, positions, cfg, kind)
        q = _lane_pad(q[:, 0], kw)
        row = cached_row(k[:, 0], v[:, 0], cfg, kv_pages[kind].dtype)
        acc, m, lsum = attention_decode_stats(
            q, flat[kind], bt[kind] + within[l] * kv_pages[kind].shape[1],
            lens, page_size=page, scale=cfg.attn_scale,
            window=cfg.sliding_window if kind else None)
        o = merge_attention_partial(acc, m, lsum, q, row[..., :kw],
                                    row[..., kw:], scale=cfg.attn_scale,
                                    sink=lp.get("sink"))
        o = o[..., :cfg.v_head_dim].astype(x.dtype)
        return o.reshape(b, 1, -1), row

    x, kept, stats, _ = _decoder(params, cfg, x, attend,
                                 (lens > 0)[:, None])
    logits = _linear(params["lm_head"], x)
    rows = jnp.arange(b)
    ring = bt[1].shape[1]
    phys = (bt[0][rows, lens // page], bt[1][rows, (lens // page) % ring])
    kv_pages = tuple(
        write_kv(kv_pages[kind], phys[kind], lens % page, new)
        for kind, new in enumerate(_stack_by_kind(cfg, kept)))
    return logits[:, 0].astype(jnp.float32), kv_pages, _none, stats


from bigdl_tpu.llm.kernels.sampling import make_sampled_step  # noqa: E402

paged_decode_step_sampled = make_sampled_step(paged_decode_step)


def _prefill_chunk(params, cfg: MimoConfig, kv_pages, toks, n_live, start,
                   bt_row, phys, slots, *, page: int):
    """One pass of the layers over ``toks`` (1, C) at positions ``start
    ..``, of which the first ``n_live`` count: attention over what the
    pools hold below ``start`` and the chunk itself, then the chunk's
    rows written page by page. Returns ``(kv_pages, x (C, H) after the
    final norm)``."""
    from bigdl_tpu.llm.kernels.hybrid_attention import prefill_attention
    from bigdl_tpu.llm.kvcache.write import write_kv_run
    c = toks.shape[1]
    flat = [_flat(p) for p in kv_pages]
    within = _class_index(cfg)
    positions = (start + jnp.arange(c, dtype=jnp.int32))[None]
    x = params["embed_tokens"][toks]
    live = jnp.arange(c)[None] < n_live
    kw = cfg.k_width

    def attend(l, kind, lp, h):
        q, k, v = project_qkv(lp, h, positions, cfg, kind)
        # attend at pool precision, as a later decode step will read it
        row = cached_row(k, v, cfg, kv_pages[kind].dtype)
        o = prefill_attention(
            _lane_pad(q, kw), row[..., :kw], row[..., kw:], flat[kind],
            (bt_row[kind] + within[l] * kv_pages[kind].shape[1])[None],
            start[None], n_live[None], lp.get("sink"), page_size=page,
            scale=cfg.attn_scale,
            window=cfg.sliding_window if kind else None)
        o = o[..., :cfg.v_head_dim].astype(x.dtype)
        return o.reshape(1, c, -1), row[0]

    x, kept, _, _ = _decoder(params, cfg, x, attend, live)
    kv_pages = tuple(
        write_kv_run(kv_pages[kind], phys[kind], slots, new)
        for kind, new in enumerate(_stack_by_kind(cfg, kept)))
    return kv_pages, x[0]


def paged_prefill_ragged(params, cfg: MimoConfig, kv_pages, _none, toks,
                         length, offset, bt_row, phys, slots, fork_dst,
                         fork_src, *, page: int):
    """Prefill of one whole prompt (``offset`` 0: the features that
    would resume from cached pages refuse this family) in the engine's
    ragged-prefill shape. A bucket longer than ``cfg.prefill_chunk`` is
    taken a chunk at a time INSIDE the program (a 32k-token pass of the
    layers at once would not fit beside the weights): chunk ``c`` reads
    what chunks ``< c`` wrote, the full class through its table, the
    window class through its ring, and only as many chunks run as
    ``length`` needs. ``kv_pages``, ``bt_row`` and ``phys`` are pairs,
    full class then window class. Returns ``(kv_pages, _none,
    last_logits (V,) f32)``."""
    bucket = toks.shape[1]
    chunk = min(bucket, cfg.prefill_chunk)
    hid = cfg.hidden_size

    def one(c, kv_pages):
        at = c * chunk
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, chunk, axis=-1)
        n_live = jnp.clip(length - at, 0, chunk)
        kv_pages, x = _prefill_chunk(
            params, cfg, kv_pages, cut(toks), n_live, offset + at, bt_row,
            tuple(cut(p) for p in phys), cut(slots), page=page)
        return kv_pages, jax.lax.dynamic_index_in_dim(
            x, jnp.clip(length - 1 - at, 0, chunk - 1), 0, keepdims=True)

    if bucket == chunk:
        kv_pages, last = one(jnp.int32(0), kv_pages)
    else:
        kv_pages, last = jax.lax.fori_loop(
            0, (length + chunk - 1) // chunk,
            lambda c, carry: one(c, carry[0]),
            (kv_pages, jnp.zeros((1, hid), params["embed_tokens"].dtype)))
    logits = _linear(params["lm_head"], last)
    return kv_pages, _none, logits[0].astype(jnp.float32)


class MimoForCausalLM(CausalLMFacade):
    """Generation facade — shared driver (see models._facade)."""

    _forward = staticmethod(forward)
    _init_cache = staticmethod(init_cache)
    _init_params = staticmethod(init_params)

    @staticmethod
    def _quantize_params(params, qtype):
        raise NotImplementedError(
            "expert-stacked weights are not ggml-quantized yet; the "
            "mimo family runs bf16")
