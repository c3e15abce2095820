"""The plain float32 reference of the ``glm_moe_dsa`` block (GLM-5: MLA
with a query down-projection, DeepSeek Sparse Attention, sigmoid-routed
experts with a shared one): the yardstick that decides ``correct`` for
the configurations of that family, and the tier-1 tests' golden.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: the whole causal sequence with no cache, the indexer's
scores of every (query, key) pair, each query's selection by a stable
sort of its scores (ties to the lower position), expanded attention
under that selection, the expert sum as a plain loop over the experts
held (each applied to every token and kept where the token chose it),
no kernel and none of the program's forward code. RoPE rotates the
stored pairs ``(2i, 2i+1)`` as pairs (the program de-interleaves them
first). Only the *layout* of the program's parameters is taken from it
(``bigdl_tpu/llm/models/deepseek.py``: which array is which). A layer at
a time, a group of heads at a time and a block of queries at a time, so
that a sequence of some 30,000 tokens fits beside the weights at the
published widths.

Per layer, pre-norm residual, RMSNorm: ``c_q = RMSNorm(h W_qa)``, ``q =
c_q W_qb`` -> heads of ``nope | rope``; ``[c | k_r] = h W_kva``, ``c``
normed; ``[k_nope | v] = c W_kvb``; scores ``q.k (nope + rope)^-0.5``.
Indexer: ``q_I = c_q W_qI`` (Hi heads of Di), ``k_I = LayerNorm(h
W_kI)`` (eps 1e-6, weight and bias), RoPE on the first ``rope`` numbers
of both, ``w = h W_w Hi^-1/2 Di^-1/2``, ``I[t, s] = sum_j w[t, j]
relu(q_I[t, j] . k_I[s])`` for ``s <= t``; attention over the
``min(topk, t + 1)`` highest. Router ``s = sigmoid(h W_g^T)`` over all
the routed experts, the k largest of ``s + b`` chosen, weights ``s``
over their sum times the scaling factor; of those only the experts this
chip holds are computed, plus the shared expert.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# queries the reference selects for at a time (their (Q, Hi, T) indexer
# products), and attends at a time (their (G, Q, T) scores)
SELECT_BLOCK = 64
QUERY_BLOCK = 128
# heads whose expanded K and V (T, G, nope + rope | v) exist at a time
HEAD_GROUP = 8
# tokens an FFN takes at a time
ROW_BLOCK = 4096


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _w(wd):
    """(K, N) float32 of a linear stored (N, K)."""
    return wd["w"].astype(jnp.float32).T


def _rope_pairs(x, theta, interleaved=True, positions=None):
    """x (T, H, D) at ``positions`` (0..T-1 where not given): pairs
    ``(2i, 2i+1)`` rotated by ``pos * theta^(-2i/D)`` (``interleaved``),
    else halves ``(i, i + D/2)``."""
    t, _, d = x.shape
    if positions is None:
        positions = jnp.arange(t)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, w_gate_up, w_down):
    """A block of ``ROW_BLOCK`` tokens at a time."""
    t = x.shape[0]

    def block(xb):
        gate, up = jnp.split(xb @ w_gate_up.astype(jnp.float32), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ w_down.astype(jnp.float32)
    out = jax.lax.map(block, _blocks(x, ROW_BLOCK))
    return out.reshape(-1, out.shape[-1])[:t]


def _select(scores, topk):
    """(Q, S) float32, ``-inf`` where not allowed -> (Q, min(topk, S))
    int32: each query's ``topk`` highest finite scores by position, ties
    to the lower position (a stable sort of the negated scores), -1 past
    the positions a query may see."""
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :topk]
    keep = jnp.take_along_axis(scores, order, -1) > -jnp.inf
    return jnp.where(keep, order, -1).astype(jnp.int32)


def _blocks(a, size):
    """``a`` (T, ...) padded to whole blocks of ``size`` rows ->
    (T / size, size, ...)."""
    pad = -a.shape[0] % size
    a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
    return a.reshape((-1, size) + a.shape[1:])


def _attention(x, lp, *, nh, nope, rope, vd, lora, eps, theta, topk, hi,
               di, index_interleave):
    """The stream after the layer's attention, each query's selection
    (T, min(topk, T)) positions (-1 where it sees fewer), and the
    indexer's queries and head weights. First the indexer scores and
    selects, a block of queries at a time; then the attention runs a
    group of heads at a time (their K and V expanded for every token)
    and, inside a group, a block of queries at a time, each query's
    softmax over exactly the positions it selected."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, lp["input_layernorm"], eps)
    c_q = _rms(h @ _w(lp["q_a_proj"]), lp["q_a_layernorm"], eps)
    ckr = h @ _w(lp["kv_a_proj"])
    c = _rms(ckr[:, :lora], lp["kv_a_layernorm"], eps)
    k_r = _rope_pairs(ckr[:, None, lora:], theta)
    # the indexer
    ip = lp["indexer"]
    qi = (c_q @ _w(ip["wq_b"])).reshape(t, hi, di)
    qi = jnp.concatenate([_rope_pairs(qi[..., :rope], theta,
                                      index_interleave), qi[..., rope:]], -1)
    ki = h @ _w(ip["wk"])
    mu = ki.mean(-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                                   + 1e-6)
    ki = ki * ip["k_norm"]["w"].astype(jnp.float32) \
        + ip["k_norm"]["b"].astype(jnp.float32)
    ki = jnp.concatenate([_rope_pairs(ki[:, None, :rope], theta,
                                      index_interleave)[:, 0],
                          ki[:, rope:]], -1)
    wi = (h @ _w(ip["weights_proj"])) * (hi ** -0.5 * di ** -0.5)

    def select(args):
        qqi, wwi, p = args
        sc = jnp.einsum("qhd,sd->qhs", qqi, ki)
        sc = jnp.einsum("qhs,qh->qs", jnp.maximum(sc, 0.0), wwi)
        return _select(jnp.where(pos[None, :] <= p[:, None], sc, -jnp.inf),
                       topk)
    chosen = jax.lax.map(select, (_blocks(qi, SELECT_BLOCK),
                                  _blocks(wi, SELECT_BLOCK),
                                  _blocks(pos, SELECT_BLOCK)))
    chosen = chosen.reshape(-1, chosen.shape[-1])

    g = math.gcd(nh, HEAD_GROUP)
    # each group's columns of q_b and kv_b and its rows of o, stacked
    w_qb = _w(lp["q_b_proj"])
    w_qb = w_qb.reshape(-1, nh // g, g * (nope + rope)).transpose(1, 0, 2)
    w_kvb = _w(lp["kv_b_proj"]).reshape(lora, nh // g, g * (nope + vd))
    w_kvb = w_kvb.transpose(1, 0, 2)
    w_o = _w(lp["o_proj"]).reshape(nh // g, g * vd, -1)

    def group(out, w):
        wq, wkv, wo = w
        kv = (c @ wkv).reshape(t, g, nope + vd)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_r, (t, g, rope))], -1)
        v = kv[..., nope:]

        def attend(args):
            cq, sel, p = args
            q = (cq @ wq).reshape(-1, g, nope + rope)
            q = jnp.concatenate([q[..., :nope], _rope_pairs(
                q[..., nope:], theta, positions=p)], -1)
            rows = jnp.arange(sel.shape[0])[:, None]
            seen = jnp.zeros((sel.shape[0], t + 1), bool).at[
                rows, jnp.where(sel >= 0, sel, t)].set(True)[:, :t]
            s = jnp.einsum("qhd,khd->hqk", q, k) * (nope + rope) ** -0.5
            pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", pr, v)
        a = jax.lax.map(attend, (_blocks(c_q, QUERY_BLOCK),
                                 _blocks(chosen, QUERY_BLOCK),
                                 _blocks(pos, QUERY_BLOCK)))
        return out + a.reshape(-1, g * vd)[:t] @ wo, None
    out, _ = jax.lax.scan(group, x, (w_qb, w_kvb, w_o))
    return out, chosen[:t], qi, wi


_STATIC = ("nh", "nope", "rope", "vd", "lora", "eps", "theta", "topk", "hi",
           "di", "index_interleave")


def _probed(chosen, qi, wi, probe):
    """At the ``probe`` positions (a tuple): each one's selection (P, T)
    bool, the indexer's query and its head weights."""
    at = jnp.asarray(probe, jnp.int32)
    t = qi.shape[0]
    sel = chosen[at]
    seen = jnp.zeros((len(probe), t + 1), bool).at[
        jnp.arange(len(probe))[:, None], jnp.where(sel >= 0, sel, t)].set(
            True)[:, :t]
    return seen, qi[at], wi[at]


@functools.partial(jax.jit, static_argnames=_STATIC + ("probe",))
def _dense_layer(x, lp, probe, **kw):
    with jax.default_matmul_precision("highest"):
        x, *got = _attention(x, lp, **kw)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        return x + _swiglu(h, _w(lp["gate_up_proj"]),
                           _w(lp["down_proj"])), _probed(*got, probe)


@functools.partial(jax.jit, static_argnames=_STATIC + (
    "top_k", "scaling", "first", "held", "probe"))
def _expert_layer(x, lp, experts, layer, probe, *, top_k, scaling, first,
                  held, **kw):
    """The stream, the selections and the indexer's probed query, the
    experts each token chose (T, k), their weights and what the router
    was given (T, H). ``experts`` is the whole stack (the held routed
    experts, then the shared), read one expert of layer ``layer`` at a
    time."""
    w_gate_up, w_down = experts["w_gate_up"], experts["w_down"]
    with jax.default_matmul_precision("highest"):
        x, *got = _attention(x, lp, **kw)
        sel = _probed(*got, probe)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        s = jax.nn.sigmoid(h @ lp["router"]["w"].astype(jnp.float32).T)
        _, idx = jax.lax.top_k(s + lp["router"]["bias"], top_k)
        w = jnp.take_along_axis(s, idx, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
        table = jnp.zeros(s.shape, jnp.float32).at[
            jnp.arange(s.shape[0])[:, None], idx].set(w)

        def one(y, e):
            return y + table[:, first + e, None] * _swiglu(
                h, w_gate_up[layer, e], w_down[layer, e]), None
        y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
        for e in range(held, w_gate_up.shape[1]):
            y = y + _swiglu(h, w_gate_up[layer, e], w_down[layer, e])
        return x + y, sel, idx, w, h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ _w(head)


def glm5_logits(cfg, params, ids, routing=None, selections=None,
                probe=(0,)):
    """``(logits (T, vocab) float32, experts (Lm, T, k) int)`` of the
    full causal forward over ``ids`` (T,), no cache: row ``t`` is the
    distribution of token ``t + 1``. ``params`` in the layout of
    ``bigdl_tpu.llm.models.deepseek.init_params``. A list given as
    ``routing`` receives, per expert layer, ``(router input (T, H),
    chosen experts (T, k), their weights (T, k))``; one given as
    ``selections``, per layer, ``(the selection (P, T) bool, the
    indexer's queries (P, Hi, Di) and head weights (P, Hi) of the
    positions ``probe``)``, float32."""
    kw = dict(nh=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
              rope=cfg.qk_rope_head_dim, vd=cfg.v_head_dim,
              lora=cfg.kv_lora_rank, eps=float(cfg.rms_norm_eps),
              theta=float(cfg.rope_theta), topk=int(cfg.index_topk),
              hi=cfg.index_n_heads, di=cfg.index_head_dim,
              index_interleave=bool(cfg.indexer_rope_interleave))
    x = params["embed_tokens"][jnp.asarray(ids, jnp.int32)] \
        .astype(jnp.float32)

    def at(tree, l):
        return jax.tree_util.tree_map(lambda a: a[l], tree)

    probe = tuple(int(p) for p in probe)

    def keep(sel):
        if selections is not None:
            selections.append(tuple(np.asarray(a) for a in sel))

    for l in range(cfg.first_k_dense_replace):
        x, sel = _dense_layer(x, at(params["dense_layers"], l), probe, **kw)
        keep(sel)
    chosen = []
    for l in range(cfg.num_hidden_layers - cfg.first_k_dense_replace):
        x, sel, idx, w, h = _expert_layer(
            x, at(params["layers"], l), params["experts"], l, probe,
            top_k=cfg.num_experts_per_tok,
            scaling=float(cfg.routed_scaling_factor),
            first=int(cfg.first_expert), held=int(cfg.routed_held), **kw)
        keep(sel)
        chosen.append(np.asarray(idx))
        if routing is not None:
            routing.append((np.asarray(h), np.asarray(idx), np.asarray(w)))
    logits = _head(x, params["norm"], params["lm_head"],
                   eps=float(cfg.rms_norm_eps))
    return np.asarray(logits), np.stack(chosen)
