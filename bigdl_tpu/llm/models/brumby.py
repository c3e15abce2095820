"""Brumby decoders (``model_type`` ``brumby``; the configuration the chip
runs is Brumby-14B-Base, ISSUE 33): Qwen3-14B's block with **power
retention** in attention's place. No layer keeps a token: each holds,
for every request, a state of fixed size that is rewritten whole at
every position (:mod:`bigdl_tpu.llm.kernels.retention`).

Per layer, pre-norm residual, RMSNorm, no biases but the gate's:
``h = x + Ret(norm(x))``, ``x' = h + W_down(silu(W_gate u) * W_up u)``,
``u = norm(h)``; an untied head after a final RMSNorm.

``Ret``, for the normed input ``u_t``, KV head ``j`` and the query
heads ``i`` of its group (``num_attention_heads / num_key_value_heads``,
query head ``i`` in group ``i // group``):

- ``q = rope(norm_q(W_q u)_i, t)``, ``k = rope(norm_k(W_k u)_j, t)``,
  ``v = (W_v u)_j``: an RMSNorm a head with a learned weight of
  ``head_dim`` (Qwen3's ``q_norm`` / ``k_norm``), RoPE by halves over
  the whole head, ``rope_theta``;
- the gate ``g = log sigmoid((W_g u)_j + b_j)``, one a KV head, float32;
  ``gamma = exp(g)``;
- ``a_ts = exp(sum_{r = s+1 .. t} g_r) (q_t . k_s)^2 / head_dim`` for
  ``s <= t`` and ``y_t = sum_s a_ts v_s / (sum_s a_ts + eps)``; ``Ret =
  W_o concat_i y_i``.

That is the **attention form** (what ``tests/brumby_reference.py``
computes). The program computes the same thing from a state: with
``phi(x) . phi(y) = (x . y)^2``, ``S_t = gamma_t S_{t-1} + phi(k_t)
v_t^T`` and ``z_t = gamma_t z_{t-1} + phi(k_t)`` give ``y_t = S_t^T
phi(q_t) / (z_t . phi(q_t) + head_dim eps)``: one token at a time when
decoding (:func:`kernels.retention.retention_decode`), a chunk at a time
over a prompt (:func:`kernels.retention.retention_prefill_chunk`).

Parameters (:func:`init_params`): ``layers`` is a list with one dict a
layer (nothing is stacked and nothing is sliced: the layers are
unrolled, as the ``mimo`` family's): ``qkv_proj`` fused ``[q | k |
v]``, ``o_proj``, ``g_proj`` (float32 weight and bias), ``q_norm``,
``k_norm``, ``gate_up_proj``, ``down_proj`` and the two layer norms.

The paged engine caches in **one state class** (:func:`page_classes`)
and in no page class: see docs/KVCACHE.md "State classes".
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bigdl_tpu.llm.kernels import retention
from bigdl_tpu.llm.models._facade import CausalLMFacade
from bigdl_tpu.llm.models.llama import _linear, mlp, rms_norm, rope


@dataclasses.dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    # the normaliser's epsilon (beside the row's sum over head_dim)
    retention_eps: float = 1e-6
    # tokens of a prompt one pass of the layers takes (the engine's
    # prefill program loops over a longer prompt's chunks itself)
    prefill_chunk: int = 1024

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def state_width(self) -> int:
        return retention.state_width(self.head_dim)

    @classmethod
    def tiny(cls, vocab: int = 256, **over) -> "BrumbyConfig":
        keys = dict(vocab_size=vocab, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=6,
                    num_key_value_heads=2, head_dim=8,
                    max_position_embeddings=2048, prefill_chunk=16)
        keys.update(over)
        return cls(**keys)

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "BrumbyConfig":
        """From the keys of a ``brumby`` ``config.json`` (Qwen3's). What
        the equations above do not cover is refused by name."""
        g = hf.get
        unsupported = {
            "rope_scaling": bool(g("rope_scaling")),
            "attention_bias": bool(g("attention_bias", False)),
            "tie_word_embeddings": bool(g("tie_word_embeddings", False)),
            "use_sliding_window": bool(g("use_sliding_window", False)),
            "hidden_act != silu": g("hidden_act", "silu") != "silu",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"brumby config uses {bad}, which this family does not "
                "implement")
        names = {f.name for f in dataclasses.fields(cls)}
        keys = {k: v for k, v in hf.items() if k in names and v is not None}
        keys["rope_theta"] = float(g("rope_theta", 1e6))
        return cls(**keys)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def qkv_widths(cfg: BrumbyConfig):
    d = cfg.head_dim
    return (cfg.num_attention_heads * d, cfg.num_key_value_heads * d,
            cfg.num_key_value_heads * d)


def gate_params(key, cfg: BrumbyConfig, spread: float = 0.5,
                low: float = 3.0, high: float = 6.0):
    """A layer's gate: ``sigmoid`` of a projection of spread ``spread``
    around a bias drawn evenly in ``low .. high``, so that ``gamma``
    lands in about 0.9 .. 0.999 as a trained gate does (a state that
    remembers ten to a thousand tokens)."""
    kw, kb = jax.random.split(key)
    h, hkv = cfg.hidden_size, cfg.num_key_value_heads
    return {"w": jax.random.normal(kw, (hkv, h), jnp.float32)
            * (spread / math.sqrt(h)),
            "b": jax.random.uniform(kb, (hkv,), jnp.float32, low, high)}


def init_params(cfg: BrumbyConfig, seed: int = 0, dtype=jnp.bfloat16,
                back: float = None) -> Dict[str, Any]:
    """Seeded parameters, drawn where JAX's default device is: every
    linear zero-mean at unit gain, the projections back into the stream
    (``o_proj``, ``down_proj``) at ``back`` (default ``1 / sqrt(2 L)``),
    the gates as :func:`gate_params`, norms 1."""
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    if back is None:
        back = 1.0 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 5 * cfg.num_hidden_layers + 2))

    def mk(shape, fan_in, gain=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain / math.sqrt(fan_in))).astype(dtype)

    nq = cfg.num_attention_heads * d
    layers = []
    for _ in range(cfg.num_hidden_layers):
        layers.append({
            "qkv_proj": {"w": mk((sum(qkv_widths(cfg)), h), h)},
            "o_proj": {"w": mk((h, nq), nq, back)},
            "g_proj": gate_params(next(keys), cfg),
            "q_norm": jnp.ones((d,), dtype),
            "k_norm": jnp.ones((d,), dtype),
            "gate_up_proj": {"w": mk((2 * f, h), h)},
            "down_proj": {"w": mk((h, f), f, back)},
            "input_layernorm": jnp.ones((h,), dtype),
            "post_attention_layernorm": jnp.ones((h,), dtype)})
    return {"embed_tokens": mk((cfg.vocab_size, h), 1.0),
            "norm": jnp.ones((h,), dtype),
            "lm_head": {"w": mk((cfg.vocab_size, h), h)},
            "layers": layers}


# ---------------------------------------------------------------------------
# layer math
# ---------------------------------------------------------------------------

def log_gate(lp, h):
    """``log sigmoid(W_g h + b)`` (..., Hkv), float32."""
    return jax.nn.log_sigmoid(
        _linear(lp["g_proj"], h.astype(jnp.float32)))


def project(lp, h, positions, cfg: BrumbyConfig):
    """h (B, T, H) -> normed, rotated q (B, T, Hkv, group, D) and k (B,
    T, Hkv, D), v (B, T, Hkv, D), the log-gates (B, T, Hkv) float32."""
    b, t, _ = h.shape
    nq, nk, _ = qkv_widths(cfg)
    d, eps = cfg.head_dim, cfg.rms_norm_eps
    qkv = _linear(lp["qkv_proj"], h)
    q = qkv[..., :nq].reshape(b, t, -1, d)
    k = qkv[..., nq:nq + nk].reshape(b, t, -1, d)
    v = qkv[..., nq + nk:].reshape(b, t, -1, d)
    q = rope(rms_norm(q, lp["q_norm"], eps), positions, cfg.rope_theta)
    k = rope(rms_norm(k, lp["k_norm"], eps), positions, cfg.rope_theta)
    return (q.reshape(b, t, cfg.num_key_value_heads, cfg.group, d), k, v,
            log_gate(lp, h))


def _decoder(params, cfg: BrumbyConfig, x, retain):
    """The unrolled layers over the stream ``x`` (B, T, H).
    ``retain(l, lp, h)`` -> the layer's retention output (B, T, nh *
    D). Returns ``x`` after the final norm."""
    eps = cfg.rms_norm_eps
    for l, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["input_layernorm"], eps)
        x = x + _linear(lp["o_proj"], retain(l, lp, h).astype(x.dtype))
        h2 = rms_norm(x, lp["post_attention_layernorm"], eps)
        x = x + mlp(lp, h2, x.dtype)
    return rms_norm(x, params["norm"], eps)


# ---------------------------------------------------------------------------
# dense forward (generate(), the parity tests' golden)
# ---------------------------------------------------------------------------

def init_cache(cfg: BrumbyConfig, batch: int, max_len: int,
               dtype=jnp.float32) -> Dict[str, Any]:
    """A row's whole cache: the state and the normaliser of every layer
    and KV head, the same size whatever ``max_len``; always float32."""
    del max_len, dtype
    shape = (cfg.num_hidden_layers, batch, cfg.num_key_value_heads)
    return {"s": jnp.zeros(shape + (cfg.head_dim, cfg.state_width),
                           jnp.float32),
            "z": jnp.zeros(shape + (cfg.state_width,), jnp.float32),
            "pos": jnp.zeros((), jnp.int32)}


def forward(params: Dict[str, Any], cfg: BrumbyConfig, tokens: jnp.ndarray,
            cache: Dict[str, Any], positions: jnp.ndarray):
    """(B, T) tokens at ``positions`` from the states in ``cache``:
    logits (B, T, V) float32 and the cache after them (the chunked form
    over the whole of ``T``)."""
    x = params["embed_tokens"][tokens]
    s_new, z_new = [], []

    def retain(l, lp, h):
        q, k, v, g = project(lp, h, positions, cfg)
        y, s, z = jax.vmap(
            lambda *a: retention.retention_dense(
                *a, eps=cfg.retention_eps))(
            cache["s"][l], cache["z"][l], q, k, v, g)
        s_new.append(s)
        z_new.append(z)
        return y.reshape(y.shape[:2] + (-1,))

    x = _decoder(params, cfg, x, retain)
    logits = _linear(params["lm_head"], x).astype(jnp.float32)
    return logits, {"s": jnp.stack(s_new), "z": jnp.stack(z_new),
                    "pos": cache["pos"] + tokens.shape[1]}


# ---------------------------------------------------------------------------
# the paged engine's entry points
# ---------------------------------------------------------------------------

def page_classes(cfg: BrumbyConfig):
    """One state class and no page class: a slot holds, a layer and KV
    head, the state (head_dim, state_width) and the normaliser
    (state_width,), float32."""
    from bigdl_tpu.llm.kvcache.classes import StateClass
    return [StateClass("state", cfg.num_hidden_layers,
                       cfg.num_key_value_heads, cfg.state_width,
                       cfg.head_dim)]


def state_bytes_a_row(cfg: BrumbyConfig) -> int:
    """What one decode step must move of one live row's cache: every
    layer's state and normaliser, read and written."""
    return cfg.num_hidden_layers * retention.decode_bytes(
        1, cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim)


def host_step_stats(cfg: BrumbyConfig, ctx_lens) -> Dict[str, int]:
    """What the host knows of a decode step it dispatches: its live
    rows, the retention layers it runs, and the bytes of state those
    rows make the decode kernel read and write (whatever their
    contexts)."""
    rows = len(ctx_lens)
    return {"state_rows_total": rows,
            "state_layer_steps_total": cfg.num_hidden_layers,
            "state_bytes_moved_total": rows * state_bytes_a_row(cfg)}


def host_prefill_stats(cfg: BrumbyConfig, tokens: int,
                       bucket: int) -> Dict[str, int]:
    """Of a prefill of ``tokens`` positions the host dispatches in the
    program of ``bucket``: the chunks its state is carried through, a
    layer, and the positions the retention kernel computes for them (a
    chunk is computed whole, its padding too; a bucket shorter than a
    chunk is one chunk of its own length)."""
    chunk = min(bucket, cfg.prefill_chunk)
    chunks = -(-tokens // chunk) * cfg.num_hidden_layers
    return {"prefill_state_chunks_total": chunks,
            "prefill_state_positions_total": chunks * chunk}


def _flat(a):
    """(L, R, …) -> (L·R, …): a layer is addressed by offsetting its
    row, never by slicing the state."""
    return a.reshape((-1,) + a.shape[2:])


def paged_decode_step(params, cfg: BrumbyConfig, state, z, rows, lens,
                      toks, *, page: int):
    """One decode step over the state class. ``state`` (L, R, Hkv, D,
    P) and ``z`` (L, R, Hkv, P) are the class's two arrays, updated in
    place; ``rows`` (B, 1) the state row each batch row holds (0, the
    trash row, for a row that sits the step out), ``lens`` (B,) the
    positions. Returns ``(logits (B, V) f32, state, z)``."""
    del page
    b = toks.shape[0]
    shapes = state.shape, z.shape
    per_layer = state.shape[1]
    state, z = _flat(state), _flat(z)
    live = rows[:, 0] > 0
    x = params["embed_tokens"][toks][:, None]
    positions = lens[:, None].astype(jnp.int32)

    def retain(l, lp, h):
        nonlocal state, z
        q, k, v, g = project(lp, h, positions, cfg)
        y, state, z = retention.retention_decode(
            state, z, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
            rows[:, 0] + l * per_layer, live, eps=cfg.retention_eps)
        return y.reshape(b, 1, -1)

    x = _decoder(params, cfg, x, retain)
    logits = _linear(params["lm_head"], x)
    return (logits[:, 0].astype(jnp.float32), state.reshape(shapes[0]),
            z.reshape(shapes[1]))


def _prefill_chunk(params, cfg: BrumbyConfig, state, z, toks, n_live,
                   start, row, fresh):
    """One pass of the layers over ``toks`` (1, C) at positions ``start
    ..``, of which the first ``n_live`` count, the row's state carried
    through it (taken as zero where ``fresh``). ``state`` and ``z`` are
    flat. Returns ``(state, z, x (C, H) after the final norm)``."""
    c = toks.shape[1]
    per_layer = state.shape[0] // cfg.num_hidden_layers
    positions = (start + jnp.arange(c, dtype=jnp.int32))[None]
    x = params["embed_tokens"][toks]

    def retain(l, lp, h):
        nonlocal state, z
        q, k, v, g = project(lp, h, positions, cfg)
        y, state, z = retention.retention_prefill_chunk(
            state, z, q[0], k[0], v[0], g[0], row + l * per_layer, fresh,
            n_live, eps=cfg.retention_eps)
        return y.reshape(1, c, -1)

    x = _decoder(params, cfg, x, retain)
    return state, z, x[0]


def paged_prefill_ragged(params, cfg: BrumbyConfig, state, z, toks, length,
                         offset, row, phys, slots, fork_dst, fork_src, *,
                         page: int):
    """Prefill of one whole prompt in the engine's ragged-prefill shape.
    ``row`` (1,) is the state row of the slot the request was seated in;
    what that row holds is its last occupant's and is **taken as zero
    by the first chunk** (``offset`` 0: the features that would resume
    a prompt refuse this family), so a slot is zeroed by the program
    that first writes it and by nothing else. A bucket longer than
    ``cfg.prefill_chunk`` is taken a chunk at a time inside the program,
    the state carried from chunk to chunk, and only as many chunks run
    as ``length`` needs. There is nothing to scatter (``phys``,
    ``slots``) or fork. Returns ``(state, z, last_logits (V,) f32)``."""
    del phys, slots, fork_dst, fork_src, page
    bucket = toks.shape[1]
    chunk = min(bucket, cfg.prefill_chunk)
    shapes = state.shape, z.shape
    state, z = _flat(state), _flat(z)

    def one(c, state, z):
        at = c * chunk
        state, z, x = _prefill_chunk(
            params, cfg, state, z,
            jax.lax.dynamic_slice_in_dim(toks, at, chunk, axis=1),
            jnp.clip(length - at, 0, chunk), offset + at, row[0],
            (offset == 0) & (c == 0))
        return state, z, jax.lax.dynamic_index_in_dim(
            x, jnp.clip(length - 1 - at, 0, chunk - 1), 0, keepdims=True)

    if bucket == chunk:
        state, z, last = one(jnp.int32(0), state, z)
    else:
        state, z, last = jax.lax.fori_loop(
            0, (length + chunk - 1) // chunk,
            lambda c, carry: one(c, carry[0], carry[1]),
            (state, z, jnp.zeros((1, cfg.hidden_size),
                                 params["embed_tokens"].dtype)))
    logits = _linear(params["lm_head"], last)
    return (state.reshape(shapes[0]), z.reshape(shapes[1]),
            logits[0].astype(jnp.float32))


class BrumbyForCausalLM(CausalLMFacade):
    """Generation facade — shared driver (see models._facade)."""

    _forward = staticmethod(forward)
    _init_cache = staticmethod(init_cache)
    _init_params = staticmethod(init_params)

    @staticmethod
    def _quantize_params(params, qtype):
        raise NotImplementedError(
            "the brumby family runs bf16: its state is float32 and its "
            "weights are not ggml-quantized yet")
