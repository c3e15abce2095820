"""The plain float32 reference of the ``nemotron_h`` block (one mixer a
layer: Mamba-2, attention or LatentMoE): the yardstick that decides
``correct`` for the configurations of that family.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: the whole sequence at once, Mamba-2 in its **dual form**
(an explicit (queries, T) matrix of weights a head: no state, no
recurrence, no chunks), a plain softmax, the expert sum an expert at a
time over every token, no kernel, no cache, and none of the program's
forward code. Only the *layout* of the program's parameters is taken
from it (``bigdl_tpu/llm/models/nemotron_h.py``: which array is which),
because the reference has to be given the same weights. A layer at a
time and a block of query rows at a time, so that 11 layers of 4k
positions fit at the published widths (16 heads x 256 queries x T
float32 weights are 68 MB at T = 4,164).

Equations. Every layer: ``x <- x + Mixer(RMSNorm(x))``, eps
``layer_norm_epsilon``; a final RMSNorm and the head.

- ``M``: ``[z | xBC | dt] = u W_in``; ``xBC'_t = silu(sum_{j<K} w_j
  xBC_{t-K+1+j} + b)`` (zeros before position 0), split ``x`` (H, P),
  ``B``, ``C`` (G, N); ``dt = softplus(dt + dt_bias)``, ``a =
  -exp(A_log)``; ``y_{t,h} = sum_{s<=t} (C_{t,g} . B_{s,g}) exp(a_h
  sum_{r=s+1..t} dt_{r,h}) dt_{s,h} x_{s,h} + D_h x_{t,h}``, ``g = h //
  (H / G)``; ``RMSNorm`` over each of the ``G`` groups of ``y *
  silu(z)``, times a weight; ``W_out``.
- ``*``: ``nh`` query heads over ``hkv`` KV heads of ``d``, causal
  softmax at ``d ** -0.5``, no rotary and no other position signal.
- ``E``: ``s = sigmoid(u W_r)``, the ``k`` largest of ``s + b``, weights
  ``s`` over their sum times the scaling; ``l = u W_down``; ``sum_i w_i
  relu(l W_up,i)^2 W_down,i`` over the chosen experts the parameters
  hold (``first_expert ..``; all of them when uncut), projected up by
  ``W_up``; plus the shared expert ``relu(u W_s,up)^2 W_s,down``.

Departures from the published description (the configuration file's
``assumed`` lists each): no clamp of ``dt`` (the family's
``time_step_limit`` default), the grouped gated norm with the gate
first, a float32 everywhere (the checkpoint is bfloat16), one latent
down- and up-projection a layer shared by the experts, the router and
the shared expert on the stream, no position signal in attention, the
multi-token-prediction module left out.
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


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


_MAMBA = ("heads", "groups", "n", "eps")


@functools.partial(jax.jit, static_argnames=_MAMBA)
def _mamba_layer(x, lp, *, heads, groups, n, eps):
    """Returns the stream, the state a recurrence would hold after the
    last position, ``S[h] = sum_s exp(a_h sum_{r>s} dt_r) dt_s x_s
    B_s^T`` (H, P, N), built directly, and the convolution's last ``K -
    1`` inputs (K - 1, C)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        hpg = heads // groups
        u = _rms(x, lp["norm"], eps)
        zxd = u @ _w(lp["in_proj"])
        conv_w = lp["conv_w"].astype(jnp.float32)           # (K, C)
        taps, c = conv_w.shape
        di = c - 2 * groups * n
        z, xbc, dt = zxd[:, :di], zxd[:, di:di + c], zxd[:, di + c:]
        padded = jnp.concatenate([jnp.zeros((taps - 1, c)), xbc])
        act = jax.nn.silu(sum(conv_w[j] * padded[j:j + t]
                              for j in range(taps))
                          + lp["conv_b"].astype(jnp.float32))
        xs = act[:, :di].reshape(t, heads, -1)
        bm = act[:, di:di + groups * n].reshape(t, groups, n)
        cm = act[:, di + groups * n:].reshape(t, groups, n)
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
        run = jnp.cumsum(dt, axis=0) * a                    # (T, H)
        dtx = dt[..., None] * xs                            # (T, H, P)
        keys = jnp.arange(t)[None, :]
        out = []
        for q0 in range(0, t, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, t)
            seen = keys <= jnp.arange(q0, q1)[:, None]      # (qb, T)
            band = jnp.einsum("qgn,sgn->gqs", cm[q0:q1], bm)

            def group(args):
                band_g, run_g, dtx_g = args     # (qb, T) (T, hpg) (T, hpg, P)
                decay = jnp.exp(jnp.where(
                    seen[..., None],
                    run_g[q0:q1, None, :] - run_g[None, :, :], -jnp.inf))
                # (q, s, h) weights first: one product of three would
                # be free to make (q, s, h, p), 4 GB at 4k positions
                return jnp.einsum("qsh,shp->qhp", band_g[..., None] * decay,
                                  dtx_g)
            y = jax.lax.map(group, (
                band, run.reshape(t, groups, hpg).transpose(1, 0, 2),
                dtx.reshape(t, groups, hpg, -1).transpose(1, 0, 2, 3)))
            out.append(y.transpose(1, 0, 2, 3).reshape(q1 - q0, heads, -1))
        y = jnp.concatenate(out) \
            + lp["D"].astype(jnp.float32)[None, :, None] * xs
        g = y.reshape(t, -1) * jax.nn.silu(z)
        grouped = g.reshape(t, groups, -1)
        g = (grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
        ).reshape(t, -1) * lp["gate_norm"].astype(jnp.float32)
        state = jnp.einsum(
            "shp,shn->hpn", jnp.exp(run[-1][None] - run)[..., None] * dtx,
            jnp.repeat(bm, hpg, axis=1))
        return x + g @ _w(lp["out_proj"]), state, xbc[t - (taps - 1):]


_ATTN = ("nh", "hkv", "d", "eps")


@functools.partial(jax.jit, static_argnames=_ATTN)
def _attention_layer(x, lp, *, nh, hkv, d, eps):
    """Returns the stream and the layer's keys and values (T, hkv, d)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        grp = nh // hkv
        u = _rms(x, lp["norm"], eps)
        qkv = u @ _w(lp["qkv_proj"])
        q = qkv[:, :nh * d].reshape(t, nh, d)
        k = qkv[:, nh * d:(nh + hkv) * d].reshape(t, hkv, d)
        v = qkv[:, (nh + hkv) * d:].reshape(t, hkv, d)
        keys = jnp.arange(t)[None, :]
        out = []
        for q0 in range(0, t, QUERY_BLOCK):
            qb = q[q0:q0 + QUERY_BLOCK].reshape(-1, hkv, grp, d)
            seen = keys <= jnp.arange(q0, q0 + qb.shape[0])[:, None]
            s = jnp.einsum("qhgd,khd->hgqk", qb, k) * d ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
            out.append(jnp.einsum("hgqk,khd->qhgd", p, v)
                       .reshape(-1, nh * d))
        return x + jnp.concatenate(out) @ _w(lp["o_proj"]), k, v


def routed_sum(latent, h, router, w_up, w_down, *, first, top_k, scaling,
               norm_topk):
    """The experts ``first .. first + len(w_up) - 1``'s part of the
    routed sum, computed in the latent ``latent`` (T, L) float32, the
    router over all of its experts on ``h`` (T, H): ``(y (T, L), chosen
    experts (T, k), their weights (T, k))``."""
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
        return y + table[:, first + e, None] * _relu2(
            latent, w_up[e].astype(jnp.float32),
            w_down[e].astype(jnp.float32)), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                        jnp.arange(w_up.shape[0]))
    return y, idx, w


_EXPERT = ("first", "top_k", "scaling", "norm_topk", "eps")


@functools.partial(jax.jit, static_argnames=_EXPERT)
def _expert_layer(x, lp, *, first, top_k, scaling, norm_topk, eps):
    """Returns the stream, the experts each token chose (T, k), their
    weights (T, k) and what the router was given (T, H)."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, lp["norm"], eps)
        y, idx, w = routed_sum(
            h @ _w(lp["latent_down"]), h, lp["router"],
            lp["experts"]["w_up"], lp["experts"]["w_down"], first=first,
            top_k=top_k, scaling=scaling, norm_topk=norm_topk)
        shared = _relu2(h, _w(lp["shared_up"]), _w(lp["shared_down"]))
        return x + y @ _w(lp["latent_up"]) + shared, idx, w, h


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


def nemotron_h_logits(cfg, params, ids, routing=None, rows=None,
                      last=None):
    """``(logits (T, vocab) float32, experts [(T, k) int an expert
    layer])`` of the full causal forward over ``ids`` (T,), no cache:
    row ``t`` is the distribution of token ``t + 1``. ``params`` in the
    layout of ``bigdl_tpu.llm.models.nemotron_h.init_params``, whose
    expert arrays hold the experts ``cfg.first_expert ..`` and no
    others: the same share as the program's. A list given as
    ``routing`` receives, per expert layer, ``(router input (T, H),
    chosen experts (T, k), their weights (T, k))``; one given as
    ``rows``, per Mamba-2 layer ``("M", state after the last position
    (H, P, N), the convolution's last inputs (K - 1, C))`` and per
    attention layer ``("*", keys (T, hkv, d), values (T, hkv, d))``;
    with ``last`` only the logits of the last ``last`` positions are
    made."""
    x = params["embed_tokens"][jnp.asarray(ids, jnp.int32)] \
        .astype(jnp.float32)
    eps = float(cfg.layer_norm_epsilon)
    chosen = []
    for kind, lp in zip(cfg.hybrid_override_pattern, params["layers"]):
        if kind == "M":
            x, state, window = _mamba_layer(
                x, lp, heads=cfg.mamba_num_heads, groups=cfg.n_groups,
                n=cfg.ssm_state_size, eps=eps)
            if rows is not None:
                rows.append(("M", np.asarray(state), np.asarray(window)))
        elif kind == "*":
            x, k, v = _attention_layer(
                x, lp, nh=cfg.num_attention_heads,
                hkv=cfg.num_key_value_heads, d=cfg.head_dim, eps=eps)
            if rows is not None:
                rows.append(("*", np.asarray(k), np.asarray(v)))
        else:
            x, idx, w, h = _expert_layer(
                x, lp, first=int(cfg.first_expert),
                top_k=int(cfg.num_experts_per_tok),
                scaling=float(cfg.routed_scaling_factor),
                norm_topk=bool(cfg.norm_topk_prob), eps=eps)
            chosen.append(np.asarray(idx))
            if routing is not None:
                routing.append((h, np.asarray(idx), np.asarray(w)))
    if last is not None:
        x = x[-last:]
    logits = _head(x, params["norm"], params["lm_head"], eps=eps)
    return logits, chosen
