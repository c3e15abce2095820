"""The plain float32 reference, the yardstick that decides ``correct``.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: no kernels, no cache, no batching tricks, and none of the
program's forward code. The only thing taken from the program is the
*layout* of its parameters (which array is which), because the reference
has to be given the same weights.

:func:`llama_logits` is a Llama-block decoder (RMSNorm, rotate-half
RoPE, grouped-query causal attention, SwiGLU, untied head) over the q4_0
weights ``benchmark/weights.py`` makes, dequantised one layer at a time
(7B in float32 is 28 GB; a layer is 0.9 GB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QK = 32  # q4_0 group: one float scale per 32 consecutive k


# ---------------------------------------------------------------------------
# Llama-block decoder over q4_0 weights
# ---------------------------------------------------------------------------

def dequant_q4(q, scale):
    """(K/2, N) uint8 + (K/32, N) scales -> (K, N) float32.

    Byte ``i`` of a column holds k=2i in its low nibble and k=2i+1 in its
    high nibble; the stored nibble is the weight plus 8."""
    half, n = q.shape
    lo = (q & 0xF).astype(jnp.int32)
    hi = (q >> 4).astype(jnp.int32)
    w = jnp.stack([lo, hi], axis=1).reshape(2 * half, n) - 8
    return w.astype(jnp.float32) * jnp.repeat(
        scale.astype(jnp.float32), QK, axis=0)


def _weight(wd):
    """(K, N) float32 of a linear given as q4_0 planes or dense (N, K)."""
    if "q" in wd:
        return dequant_q4(wd["q"], wd["scale"])
    return wd["w"].astype(jnp.float32).T


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x (T, H, D), positions 0..T-1, rotate-half pairing (i, i + D/2)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "theta", "window"))
def _llama_layer(x, lp, *, n_heads, n_kv, eps, theta, window):
    with jax.default_matmul_precision("highest"):
        t, h = x.shape
        hd = h // n_heads
        y = _rms(x, lp["input_layernorm"], eps)
        qkv = y @ _weight(lp["qkv_proj"])
        q = _rope(qkv[:, : n_heads * hd].reshape(t, n_heads, hd), theta)
        k = _rope(qkv[:, n_heads * hd: (n_heads + n_kv) * hd]
                  .reshape(t, n_kv, hd), theta)
        v = qkv[:, (n_heads + n_kv) * hd:].reshape(t, n_kv, hd)
        rep = n_heads // n_kv
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        keep = j <= i
        if window:
            keep &= j > i - window
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h)
        x = x + a @ _weight(lp["o_proj"])
        y = _rms(x, lp["post_attention_layernorm"], eps)
        gu = y @ _weight(lp["gate_up_proj"])
        gate, up = jnp.split(gu, 2, axis=-1)
        return x + (jax.nn.silu(gate) * up) @ _weight(lp["down_proj"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _llama_head(x, norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ _weight(head)


def llama_logits(cfg, params, ids) -> np.ndarray:
    """(T, vocab) float32 logits of the full causal forward over ``ids``
    (T,), no cache: row ``t`` is the distribution of token ``t + 1``.
    ``params`` is the fused stacked layout (``qkv_proj``,
    ``gate_up_proj`` with a leading layer axis)."""
    x = params["embed_tokens"][jnp.asarray(ids, jnp.int32)] \
        .astype(jnp.float32)
    for l in range(cfg.num_hidden_layers):
        lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        x = _llama_layer(
            x, lp, n_heads=cfg.num_attention_heads,
            n_kv=cfg.num_key_value_heads, eps=float(cfg.rms_norm_eps),
            theta=float(cfg.rope_theta),
            window=int(cfg.sliding_window or 0))
    return np.asarray(_llama_head(x, params["norm"], params["lm_head"],
                                  eps=float(cfg.rms_norm_eps)))


def margins(logits: np.ndarray, tokens) -> np.ndarray:
    """How far below the row's maximum each chosen token's logit lies,
    in units of that row's standard deviation (0 = it is the argmax)."""
    logits = np.asarray(logits, np.float64)
    rows = np.arange(len(tokens))
    chosen = logits[rows, np.asarray(tokens)]
    return (logits.max(-1) - chosen) / logits.std(-1)
