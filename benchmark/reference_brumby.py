"""The plain float32 reference of the ``brumby`` block (Qwen3's block
with power retention in attention's place): the yardstick that decides
``correct`` for the configurations of that family.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: the whole sequence in the **attention form**, an explicit
(queries, T) matrix of weights a head, no state, no chunks, no kernel,
no ``phi``, and none of the program's forward code. Only the *layout*
of the program's parameters is taken from it
(``bigdl_tpu/llm/models/brumby.py``: which array is which), because the
reference has to be given the same weights. A layer at a time and a
block of query rows at a time, so that 8 layers of 4k positions fit at
the published widths (40 heads x 256 queries x T float32 weights are
168 MB at T = 4,096).

Equations (per layer, pre-norm residual, RMSNorm eps): ``[q | k | v] =
u W_qkv`` -> ``nh`` query heads and ``hkv`` key and value heads of
``d``; q and k RMS-normed a head (learned weight of ``d``), then RoPE
over all ``d`` numbers, pairs ``(i, i + d/2)``, theta ``rope_theta``;
the gate ``g_t = log sigmoid(u_t W_g + b_g)``, one a KV head; for ``s
<= t``: ``a_ts = exp(sum_{r = s+1 .. t} g_r) (q_t . k_s)^2 / d``; ``y_t
= sum_s a_ts v_s / (sum_s a_ts + eps)``; output ``(nh * d) -> h``. Query
head ``i`` reads KV head ``i // (nh / hkv)``. Feed-forward: a SwiGLU.

What the published ``config.json`` does not say, and is assumed (the
configuration file lists each): power 2, one gate a KV head with a
bias, the normaliser by the row's sum with ``eps`` = 1e-6, q/k norms
and rotary kept as Qwen3 has them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
HEAD_BLOCK = 16384


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _w(wd):
    """(K, N) float32 of a linear stored (N, K)."""
    return wd["w"].astype(jnp.float32).T


def _rope(x, theta):
    """x (T, H, D) at positions 0..T-1, rotated by halves."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _retention(x, lp, *, nh, hkv, d, theta, eps, ret_eps):
    t = x.shape[0]
    grp = nh // hkv
    u = _rms(x, lp["input_layernorm"], eps)
    qkv = u @ _w(lp["qkv_proj"])
    q = qkv[:, :nh * d].reshape(t, nh, d)
    k = qkv[:, nh * d:(nh + hkv) * d].reshape(t, hkv, d)
    v = qkv[:, (nh + hkv) * d:].reshape(t, hkv, d)
    q = _rope(_rms(q, lp["q_norm"], eps), theta)
    k = _rope(_rms(k, lp["k_norm"], eps), theta)
    g = jax.nn.log_sigmoid(u @ _w(lp["g_proj"])
                           + lp["g_proj"]["b"].astype(jnp.float32))
    run = jnp.cumsum(g, axis=0)                             # (T, hkv)
    keys = jnp.arange(t)[None, :]
    out = []
    for q0 in range(0, t, QUERY_BLOCK):
        qb = q[q0:q0 + QUERY_BLOCK].reshape(-1, hkv, grp, d)
        at = jnp.arange(q0, q0 + qb.shape[0])[:, None]
        seen = (keys <= at)[None]                           # (1, qb, T)
        # exp(sum of the gates after s up to t), 0 where s > t
        decay = jnp.exp(jnp.where(
            seen, run[q0:q0 + qb.shape[0]].T[:, :, None]
            - run.T[:, None, :], -jnp.inf))                 # (hkv, qb, T)
        a = jnp.einsum("qhgd,khd->hgqk", qb, k) ** 2 / d \
            * decay[:, None]
        y = jnp.einsum("hgqk,khd->qhgd", a, v) \
            / (a.sum(-1) + ret_eps).transpose(2, 0, 1)[..., None]
        out.append(y.reshape(-1, nh * d))
    return x + jnp.concatenate(out) @ _w(lp["o_proj"]), k, v, g


_RET = ("nh", "hkv", "d", "theta", "eps", "ret_eps")


@functools.partial(jax.jit, static_argnames=_RET)
def _layer(x, lp, **kw):
    """Returns the stream and the layer's keys (T, hkv, d), values (T,
    hkv, d) and log-gates (T, hkv): what a state is built from."""
    with jax.default_matmul_precision("highest"):
        x, k, v, g = _retention(x, lp, **kw)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        gate, up = jnp.split(h @ _w(lp["gate_up_proj"]), 2, axis=-1)
        return x + (jax.nn.silu(gate) * up) @ _w(lp["down_proj"]), k, v, g


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, norm, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ rows.astype(jnp.float32).T


def _head(x, norm, head, *, eps):
    """The output head, a block of vocabulary rows at a time, each
    block's logits taken to the host before the next is made."""
    w = head["w"]
    return np.concatenate([
        np.asarray(_head_block(x, norm, w[v0:v0 + HEAD_BLOCK], eps=eps))
        for v0 in range(0, w.shape[0], HEAD_BLOCK)], axis=-1)


def brumby_logits(cfg, params, ids, rows=None, last=None):
    """``logits (T, vocab) float32`` of the full causal forward over
    ``ids`` (T,), no state: row ``t`` is the distribution of token ``t
    + 1``. ``params`` in the layout of
    ``bigdl_tpu.llm.models.brumby.init_params``. A list given as
    ``rows`` receives, per layer, its ``(keys (T, hkv, d), values (T,
    hkv, d), log-gates (T, hkv))``; with ``last`` only the logits of
    the last ``last`` positions are made."""
    x = params["embed_tokens"][jnp.asarray(ids, jnp.int32)] \
        .astype(jnp.float32)
    kw = dict(nh=cfg.num_attention_heads, hkv=cfg.num_key_value_heads,
              d=cfg.head_dim, theta=float(cfg.rope_theta),
              eps=float(cfg.rms_norm_eps),
              ret_eps=float(cfg.retention_eps))
    for lp in params["layers"]:
        x, k, v, g = _layer(x, lp, **kw)
        if rows is not None:
            rows.append((np.asarray(k), np.asarray(v), np.asarray(g)))
    if last is not None:
        x = x[-last:]
    return _head(x, params["norm"], params["lm_head"],
                 eps=float(cfg.rms_norm_eps))


@jax.jit
def _state_of_layer(k, v, g):
    with jax.default_matmul_precision("highest"):
        run = jnp.cumsum(g, axis=0)
        decay = jnp.exp(run[-1][None] - run)                # (T, hkv)
        wk = decay[..., None] * k

        def head(args):
            wk_h, k_h, v_h = args                           # (T, ·)
            kk = wk_h[:, :, None] * k_h[:, None, :]         # (T, d, d)
            return jnp.einsum("tab,tv->abv", kk, v_h), kk.sum(0)
        return jax.lax.map(head, tuple(
            a.transpose(1, 0, 2) for a in (wk, k, v)))


def state_of(k, v, g, upto=None):
    """The state and the normaliser a retention layer would hold after
    position ``upto - 1``, built directly (float32, highest precision,
    no recurrence and no ``phi``) from a layer's keys, values and
    log-gates: ``S[h, a, b, :] = sum_s decay_s k_s[a] k_s[b] v_s`` as
    the full ``(d, d)`` square a head, ``z[h, a, b] = sum_s decay_s
    k_s[a] k_s[b]``, ``decay_s = exp(sum_{r > s} g_r)``. Returns ``(S
    (hkv, d, d, dv), z (hkv, d, d))`` on the host."""
    s, z = _state_of_layer(*(jnp.asarray(a, jnp.float32)[:upto]
                             for a in (k, v, g)))
    return np.asarray(s), np.asarray(z)
