"""The plain float32 reference of the ``mimo_v2`` block (full-attention
and sliding-window layers mixed, K 192 / V 128, a sink on the window
layers, sigmoid-routed experts of which a range is held): the yardstick
that decides ``correct`` for the configurations of that family.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: the whole sequence with no cache, an explicit (queries,
T) mask a layer kind, the sink as one more softmax column, the expert
sum as a plain loop over the held experts (each applied to every token
and kept where the token chose it), no kernel, no sorting, no batching,
and none of the program's forward code. Only the *layout* of the
program's parameters is taken from it (``bigdl_tpu/llm/models/mimo.py``:
which array is which), because the reference has to be given the same
weights. A layer at a time and a block of query rows at a time, so that
1-2k positions fit at the published widths (64 heads x 256 queries x T
float32 scores are 71 MB at T = 1,088).

Equations (per layer, pre-norm residual, RMSNorm eps): ``[q | k | v] =
h W_qkv`` -> ``nh`` query heads and ``hkv`` key heads of ``d``, ``hkv``
value heads of ``dv`` (``hkv`` = ``num_key_value_heads`` on a full
layer, ``swa_num_key_value_heads`` on a window layer); RoPE on the first
``rot = int(partial_rotary_factor * d)`` numbers of every q and k head,
pairs ``(i, i + rot/2)``, theta ``rope_theta`` | ``swa_rope_theta``;
``v <- attention_value_scale * v``; scores ``q.k / sqrt(d)``; a query at
``t`` sees keys ``<= t`` (full) or ``t - W + 1 .. t`` (window); on a
window layer ``p_j = exp(a_j) / (sum_i exp(a_i) + exp(s_h))`` with the
head's sink ``s_h``; output ``(nh * dv) -> h``. Feed-forward: a dense
SwiGLU, or router ``s = sigmoid(h W_r^T)``, the k largest of ``s + b``
chosen, weights ``s`` over their sum (+1e-20), times the scaling factor
(1), and ``y = sum over the chosen experts THAT ARE HELD of w_e E_e(h)``.

Departures from the published description, each because the catalog's
``config`` has no key that says otherwise:

- the rotary pairing is by halves over the ``rot`` rotary numbers (the
  family's convention; the checkpoint's own layout is not in the config);
- the value scale multiplies the projected value (before the cache), not
  the attention output: the same function, stated so that "what is
  cached" is defined;
- the vision and audio towers and the multi-token-prediction layers are
  left out: text in, one next-token distribution out;
- **the share**: of ``n_routed_experts`` experts only ``experts`` =
  ``(first, count)`` are computed (default: the range the configuration
  holds), as on one chip of a deployment that divides each layer's
  experts over several; what the other experts would have added is left
  out, and that partial sum is what goes on to the next layer. The
  router is whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _w(wd):
    """(K, N) float32 of a linear stored (N, K)."""
    return wd["w"].astype(jnp.float32).T


def _rope(x, theta, rot):
    """x (T, H, D) at positions 0..T-1: the first ``rot`` numbers
    rotated by halves, the rest as they are."""
    t = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], -1)


def _swiglu(x, w_gate_up, w_down):
    gu = x @ w_gate_up.astype(jnp.float32)
    gate, up = jnp.split(gu, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down.astype(jnp.float32)


def _attention(x, lp, *, nh, hkv, d, dv, rot, theta, window, value_scale,
               eps):
    """``window`` 0: a full layer; else the positions a query sees."""
    t = x.shape[0]
    g = nh // hkv
    h = _rms(x, lp["input_layernorm"], eps)
    qkv = h @ _w(lp["qkv_proj"])
    q = _rope(qkv[:, :nh * d].reshape(t, nh, d), theta, rot)
    k = _rope(qkv[:, nh * d:(nh + hkv) * d].reshape(t, hkv, d), theta, rot)
    v = qkv[:, (nh + hkv) * d:].reshape(t, hkv, dv) * value_scale
    keys = jnp.arange(t)[None, :]
    out = []
    for q0 in range(0, t, QUERY_BLOCK):
        qb = q[q0:q0 + QUERY_BLOCK].reshape(-1, hkv, g, d)
        at = jnp.arange(q0, q0 + qb.shape[0])[:, None]
        keep = keys <= at                                   # (qb, T)
        if window:
            keep &= keys > at - window
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) * d ** -0.5
        s = jnp.where(keep[None, None], s, -jnp.inf)
        if "sink" in lp:
            col = jnp.broadcast_to(
                lp["sink"].astype(jnp.float32).reshape(hkv, g, 1, 1),
                s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("hgqk,khd->qhgd", p, v)
                   .reshape(-1, nh * dv))
    return x + jnp.concatenate(out) @ _w(lp["o_proj"]), k, v


_ATTN = ("nh", "hkv", "d", "dv", "rot", "theta", "window", "value_scale",
         "eps")


@functools.partial(jax.jit, static_argnames=_ATTN)
def _dense_layer(x, lp, **kw):
    """Returns the stream and the layer's rotated keys (T, hkv, d) and
    scaled values (T, hkv, dv): what a cache would hold."""
    with jax.default_matmul_precision("highest"):
        x, k, v = _attention(x, lp, **kw)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        return x + _swiglu(h, _w(lp["gate_up_proj"]),
                           _w(lp["down_proj"])), k, v


def routed_sum(h, router, w_gate_up, w_down, *, first, top_k, scaling,
               norm_topk):
    """The experts ``first .. first + len(w_gate_up) - 1``'s part of the
    routed sum for ``h`` (T, H) float32, the router over all of its
    experts: ``(y, chosen experts (T, k), their weights (T, k))``."""
    s = jax.nn.sigmoid(h @ router["w"].astype(jnp.float32).T)
    _, idx = jax.lax.top_k(s + router["bias"], top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * scaling
    # dense (T, E) table of weights: 0 where the token did not choose
    # the expert
    table = jnp.zeros(s.shape, jnp.float32).at[
        jnp.arange(s.shape[0])[:, None], idx].set(w)

    def one(y, e):
        return y + table[:, first + e, None] * _swiglu(
            h, w_gate_up[e], w_down[e]), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        jnp.arange(w_gate_up.shape[0]))
    return y, idx, w


@functools.partial(jax.jit, static_argnames=_ATTN + (
    "first", "top_k", "scaling", "norm_topk"))
def _expert_layer(x, lp, *, first, top_k, scaling, norm_topk, **kw):
    """Returns the stream, the experts each token chose (T, k), their
    weights (T, k), what the router was given (T, H) and the layer's
    keys and values as :func:`_dense_layer` does."""
    with jax.default_matmul_precision("highest"):
        x, k, v = _attention(x, lp, **kw)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        y, idx, w = routed_sum(
            h, lp["router"], lp["experts"]["w_gate_up"],
            lp["experts"]["w_down"], first=first, top_k=top_k,
            scaling=scaling, norm_topk=norm_topk)
        return x + y, idx, w, h, k, v


HEAD_BLOCK = 16384


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, norm, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ rows.astype(jnp.float32).T


def _head(x, norm, head, *, eps):
    """The output head, a block of vocabulary rows at a time, each
    block's logits taken to the host before the next is made: neither a
    float32 copy of the whole head (2.5 GB at 152,576 x 4,096) nor the
    logits twice over (their blocks and their concatenation, 1.3 GB at
    1,087 positions) ever exist on the device."""
    w = head["w"]
    return np.concatenate([
        np.asarray(_head_block(x, norm, w[v0:v0 + HEAD_BLOCK], eps=eps))
        for v0 in range(0, w.shape[0], HEAD_BLOCK)], axis=-1)


def mimo_logits(cfg, params, ids, routing=None, rows=None):
    """``(logits (T, vocab) float32, experts [(T, k) int an expert
    layer])`` of the full causal forward over ``ids`` (T,), no cache:
    row ``t`` is the distribution of token ``t + 1``. ``params`` in the
    layout of ``bigdl_tpu.llm.models.mimo.init_params``, whose expert
    arrays hold the experts ``cfg.first_expert ..`` and no others: the
    same share as the program's. A list given as ``routing`` receives,
    per expert layer, ``(router input (T, H), chosen experts (T, k),
    their weights (T, k))``; one given as ``rows``, per layer, its
    ``(keys (T, hkv, d), values (T, hkv, dv))``."""
    x = params["embed_tokens"][jnp.asarray(ids, jnp.int32)] \
        .astype(jnp.float32)
    chosen = []
    for l, lp in enumerate(params["layers"]):
        swa = bool(cfg.hybrid_layer_pattern[l])
        kw = dict(
            nh=cfg.num_attention_heads,
            hkv=cfg.swa_num_key_value_heads if swa
            else cfg.num_key_value_heads,
            d=cfg.head_dim, dv=cfg.v_head_dim,
            rot=int(cfg.partial_rotary_factor * cfg.head_dim),
            theta=float(cfg.swa_rope_theta if swa else cfg.rope_theta),
            window=int(cfg.sliding_window) if swa else 0,
            value_scale=float(cfg.attention_value_scale),
            eps=float(cfg.rms_norm_eps))
        if not cfg.moe_layer_freq[l]:
            x, k, v = _dense_layer(x, lp, **kw)
        else:
            x, idx, w, h, k, v = _expert_layer(
                x, lp, first=int(cfg.first_expert),
                top_k=int(cfg.num_experts_per_tok),
                scaling=float(cfg.routed_scaling_factor),
                norm_topk=bool(cfg.norm_topk_prob), **kw)
        if rows is not None:
            rows.append((np.asarray(k), np.asarray(v)))
        if not cfg.moe_layer_freq[l]:
            continue
        chosen.append(np.asarray(idx))
        if routing is not None:
            routing.append((h, np.asarray(idx), np.asarray(w)))
    logits = _head(x, params["norm"], params["lm_head"],
                   eps=float(cfg.rms_norm_eps))
    return logits, chosen


def router_on_reference_inputs(route, params, routing):
    """The program's own router (``route(router_params, h)`` ->
    experts, weights) on the float32 inputs the reference's router was
    given, against the reference's routing: ``(share of (token, layer)
    pairs with the same experts, largest relative difference of a
    weight on those pairs)``. With the same inputs a float32 router
    agrees but for exact ties; one that rounds its scores, leaves an
    expert out or weighs otherwise does not, however the streams of the
    two forwards have drifted apart."""
    routers = [lp["router"] for lp in params["layers"] if "router" in lp]
    same, worst = [], 0.0
    for lp, (h, idx, w) in zip(routers, routing):
        got_idx, got_w = (np.asarray(a) for a in route(lp, h))
        if got_idx.shape != idx.shape:
            same.append(np.zeros(len(idx), bool))
            continue
        order, got_order = np.argsort(idx, -1), np.argsort(got_idx, -1)
        hit = (np.take_along_axis(idx, order, -1)
               == np.take_along_axis(got_idx, got_order, -1)).all(-1)
        same.append(hit)
        if hit.any():
            a = np.take_along_axis(w, order, -1)[hit]
            b = np.take_along_axis(got_w, got_order, -1)[hit]
            worst = max(worst, float(np.abs(a / b - 1).max()))
    return float(np.mean(same)), worst


def same_experts(a, b) -> np.ndarray:
    """(expert layers, T) bool: the two choices, lists of (T, k) a
    layer, name the same set."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return np.zeros(a.shape[:2], bool)
    return (np.sort(a, -1) == np.sort(b, -1)).all(-1)
