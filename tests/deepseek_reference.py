"""The plain float32 reference of the ``deepseek_v3`` block (MLA without
a query down-projection, sigmoid-routed experts with shared ones): the
yardstick that decides ``correct`` for the configurations of that family.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: the expanded (non-absorbed) attention over the whole
sequence with no cache, the expert sum as a plain loop over the experts
(each applied to every token and kept where the token chose it), no
kernel, no sorting, no batching, and none of the program's forward
code. Only the *layout* of the program's parameters is taken from it
(``bigdl_tpu/llm/models/deepseek.py``: which array is which), because
the reference has to be given the same weights. A layer at a time, so
that float32 copies of one layer's experts (2.4 GB at 128 x 768 x 2048)
are all that is ever held.

Equations (per layer, pre-norm residual, RMSNorm): ``q = h W_q`` ->
heads of ``nope | rope``; ``[c | k_r] = h W_kva``; ``c`` normed; RoPE on
every head's ``q_rope`` and on the shared ``k_r``, the stored pairs
``(2i, 2i+1)`` de-interleaved to ``(i, i + d/2)`` and rotated by halves;
``[k_nope | v] = c W_kvb`` per head; scores ``q.k (nope + rope)^-0.5``,
causal softmax; router ``s = sigmoid(h W_g^T)``, the k largest of
``s + b`` chosen, weights ``s`` over their sum (+1e-20) times the
scaling factor; ``y = sum_k w_k E_k(h) + E_shared(h)``.

``fault`` (for showing that the comparison can fail, never for a
result): ``router_bf16``, ``top5``, ``no_shared``, ``weights_from_s_plus_b``,
``k_rope_unrotated``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("router_bf16", "top5", "no_shared", "weights_from_s_plus_b",
          "k_rope_unrotated")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _w(wd):
    """(K, N) float32 of a linear stored (N, K)."""
    return wd["w"].astype(jnp.float32).T


def _rope_pairs(x, theta):
    """x (T, H, D) with pairs stored (2i, 2i+1), positions 0..T-1:
    de-interleave, then rotate halves."""
    t, _, d = x.shape
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, w_gate_up, w_down):
    gu = x @ w_gate_up.astype(jnp.float32)
    gate, up = jnp.split(gu, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down.astype(jnp.float32)


def _attention(x, lp, *, nh, nope, rope, vd, lora, eps, theta, fault):
    t = x.shape[0]
    h = _rms(x, lp["input_layernorm"], eps)
    q = (h @ _w(lp["q_proj"])).reshape(t, nh, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], theta)
    ckr = h @ _w(lp["kv_a_proj"])
    c = _rms(ckr[:, :lora], lp["kv_a_layernorm"], eps)
    k_r = ckr[:, None, lora:]
    if fault == "k_rope_unrotated":
        k_r = jnp.concatenate([k_r[..., 0::2], k_r[..., 1::2]], -1)
    else:
        k_r = _rope_pairs(k_r, theta)
    kv = (c @ _w(lp["kv_b_proj"])).reshape(t, nh, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (t, nh, rope))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("qhd,khd->hqk", qf, k) * (nope + rope) ** -0.5
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:]).reshape(t, nh * vd)
    return x + a @ _w(lp["o_proj"])


@functools.partial(jax.jit, static_argnames=(
    "nh", "nope", "rope", "vd", "lora", "eps", "theta", "fault"))
def _dense_layer(x, lp, **kw):
    with jax.default_matmul_precision("highest"):
        x = _attention(x, lp, **kw)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        return x + _swiglu(h, _w(lp["gate_up_proj"]), _w(lp["down_proj"]))


@functools.partial(jax.jit, static_argnames=(
    "nh", "nope", "rope", "vd", "lora", "eps", "theta", "fault", "n_exp",
    "top_k", "scaling", "norm_topk"))
def _expert_layer(x, lp, experts, layer, *, n_exp, top_k, scaling,
                  norm_topk, fault, **kw):
    """Returns the stream, the experts each token chose (T, k), their
    weights (T, k) and what the router was given (T, H). ``experts`` is
    the whole stack, read one expert of layer ``layer`` at a time."""
    w_gate_up, w_down = experts["w_gate_up"], experts["w_down"]
    with jax.default_matmul_precision("highest"):
        x = _attention(x, lp, fault=fault, **kw)
        h = _rms(x, lp["post_attention_layernorm"], kw["eps"])
        wg, hr = lp["router"]["w"].astype(jnp.float32), h
        if fault == "router_bf16":
            wg, hr = wg.astype(jnp.bfloat16), h.astype(jnp.bfloat16)
        s = jax.nn.sigmoid((hr @ wg.T).astype(jnp.float32))
        biased = s + lp["router"]["bias"]
        k = top_k - 1 if fault == "top5" else top_k
        _, idx = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(
            biased if fault == "weights_from_s_plus_b" else s, idx, -1)
        if norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * scaling
        # dense (T, E) table of weights: 0 where the token did not
        # choose the expert
        table = jnp.zeros(s.shape, jnp.float32).at[
            jnp.arange(s.shape[0])[:, None], idx].set(w)

        def one(y, e):
            return y + table[:, e, None] * _swiglu(
                h, w_gate_up[layer, e], w_down[layer, e]), None
        y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n_exp))
        if fault != "no_shared":
            for e in range(n_exp, w_gate_up.shape[1]):
                y = y + _swiglu(h, w_gate_up[layer, e], w_down[layer, e])
        return x + y, idx, w, h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ _w(head)


def deepseek_logits(cfg, params, ids, fault: str = "", routing=None):
    """``(logits (T, vocab) float32, experts (Lm, T, k) int)`` of the
    full causal forward over ``ids`` (T,), no cache: row ``t`` is the
    distribution of token ``t + 1``. ``params`` in the layout of
    ``bigdl_tpu.llm.models.deepseek.init_params``. A list given as
    ``routing`` receives, per expert layer, ``(router input (T, H),
    chosen experts (T, k), their weights (T, k))``."""
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    kw = dict(nh=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
              rope=cfg.qk_rope_head_dim, vd=cfg.v_head_dim,
              lora=cfg.kv_lora_rank, eps=float(cfg.rms_norm_eps),
              theta=float(cfg.rope_theta), fault=fault)
    x = params["embed_tokens"][jnp.asarray(ids, jnp.int32)] \
        .astype(jnp.float32)

    def at(tree, l):
        return jax.tree_util.tree_map(lambda a: a[l], tree)

    for l in range(cfg.first_k_dense_replace):
        x = _dense_layer(x, at(params["dense_layers"], l), **kw)
    chosen = []
    for l in range(cfg.num_hidden_layers - cfg.first_k_dense_replace):
        x, idx, w, h = _expert_layer(
            x, at(params["layers"], l), params["experts"], l,
            n_exp=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            scaling=float(cfg.routed_scaling_factor),
            norm_topk=bool(cfg.norm_topk_prob), **kw)
        chosen.append(np.asarray(idx))
        if routing is not None:
            routing.append((h, np.asarray(idx), np.asarray(w)))
    logits = _head(x, params["norm"], params["lm_head"],
                   eps=float(cfg.rms_norm_eps))
    return np.asarray(logits), np.stack(chosen)


def router_on_reference_inputs(route, params, routing):
    """The program's own router (``route(router_params, h)`` ->
    experts, weights) on the float32 inputs the reference's router was
    given, against the reference's routing: ``(share of (token, layer)
    pairs with the same experts, largest relative difference of a
    weight on those pairs)``. With the same inputs a float32 router
    agrees but for exact ties; one that rounds its scores, leaves an
    expert out or weighs by the biased scores does not, however the
    streams of the two forwards have drifted apart."""
    same, worst = [], 0.0
    for l, (h, idx, w) in enumerate(routing):
        lp = jax.tree_util.tree_map(lambda a: a[l],
                                    params["layers"]["router"])
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
    """(Lm, T) bool: the two (Lm, T, k) choices name the same set."""
    if a.shape != b.shape:
        return np.zeros(a.shape[:2], bool)
    return (np.sort(a, -1) == np.sort(b, -1)).all(-1)
