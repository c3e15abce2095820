"""Deliberate faults planted in the SERVED program of the ``nemotron_h``
family, to show that the driver's comparison
(``drivers/serve_nemotron_h``) comes out ``correct: false`` for each.
Never for a result: ``benchmark/check_nemotron_h.py`` (chip, published
widths), ``benchmark/tests/test_nemotron_h_cell.py`` and
``tests/test_nemotron_h.py`` (CPU, tiny widths) are the only users.

A fault replaces one function of ``bigdl_tpu.llm.models.nemotron_h`` or
of its kernels (``kernels.ssm``, ``kernels.moe``) while an
``LLMServer`` is built and driven, and is taken out again; the kernels
the engine runs stay the served ones. ISSUE 37's fifteen, then one for
the router's precision:

- ``window_not_zeroed``: a newly seated slot's convolution window NOT
  taken as zero (it poisons the first 3 positions and a long prompt
  hides it);
- ``state_not_zeroed``: its state matrix not taken as zero;
- ``state_bf16``: the state rounded to bfloat16 whenever it is written
  back (a lower precision than the configuration states must fail one
  check);
- ``no_skip``: ``D x`` left out;
- ``no_conv_bias``: the convolution's bias left out;
- ``no_softplus``: the time step ``dt + dt_bias`` taken as it is;
- ``norm_ungrouped``: the gated norm over all of ``d_inner`` and not by
  groups;
- ``gate_after_norm``: ``RMSNorm(y) * silu(z)`` for ``RMSNorm(y *
  silu(z))``;
- ``rotary``: rotary applied to q and k (``rope_theta``, by halves);
- ``relu_for_relu2``: the experts' and the shared expert's ``relu(.)``
  not squared;
- ``top21``: one expert fewer than ``num_experts_per_tok``;
- ``renorm_over_held``: routed weights renormalised over the held
  experts only;
- ``no_scaling``: ``routed_scaling_factor`` left out;
- ``experts_next_share``: the held weights used as the next share's
  experts;
- ``shared_from_latent``: the shared expert fed the stream rebuilt from
  the latent (``W_up (W_down u)``) and not the stream itself;
- ``router_bf16``: the router's scores in bfloat16.
"""

from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("window_not_zeroed", "state_not_zeroed", "state_bf16", "no_skip",
          "no_conv_bias", "no_softplus", "norm_ungrouped",
          "gate_after_norm", "rotary", "relu_for_relu2", "top21",
          "renorm_over_held", "no_scaling", "experts_next_share",
          "shared_from_latent", "router_bf16")


def _route(fault: str):
    import jax
    import jax.numpy as jnp

    def route(router, h, cfg):
        w, x = router["w"].astype(jnp.float32), h.astype(jnp.float32)
        if fault == "router_bf16":
            w, x = w.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        s = jax.nn.sigmoid(jnp.dot(
            x, w.T, precision=jax.lax.Precision.HIGHEST)
            .astype(jnp.float32))
        k = cfg.num_experts_per_tok - (fault == "top21")
        _, idx = jax.lax.top_k(s + router["bias"], k)
        wts = jnp.take_along_axis(s, idx, -1)
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
        scale = 1.0 if fault == "no_scaling" else cfg.routed_scaling_factor
        return idx.astype(jnp.int32), wts * scale
    return route


@contextlib.contextmanager
def planted(fault: str, cfg=None):
    """The program with ``fault`` in it; every compiled engine program
    is dropped on the way in and out, since the engine caches them by
    shape and not by what they compute."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm import serving
    from bigdl_tpu.llm.kernels import moe, ssm
    from bigdl_tpu.llm.models import nemotron_h as nh
    from bigdl_tpu.llm.models.llama import _linear, rope

    del cfg
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault in ("window_not_zeroed", "state_not_zeroed"):
        left = "conv" if fault == "window_not_zeroed" else "state"
        patch = mock.patch.object(
            nh, "taken_as_zero",
            lambda array, fresh: fresh & (array != left))
    elif fault == "state_bf16":
        patch = mock.patch.object(
            ssm, "_held",
            lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    elif fault == "no_skip":
        patch = mock.patch.object(nh, "skip_of", lambda lp: 0.0 * lp["D"])
    elif fault == "no_conv_bias":
        patch = mock.patch.object(nh, "conv_bias_of",
                                  lambda lp: 0.0 * lp["conv_b"])
    elif fault == "no_softplus":
        patch = mock.patch.object(
            nh, "time_step",
            lambda dt, bias: dt.astype(jnp.float32) + bias)
    elif fault == "norm_ungrouped":
        def ungrouped(y, z, w, c):
            g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            var = jnp.mean(g * g, axis=-1, keepdims=True)
            return (g * jax.lax.rsqrt(var + c.layer_norm_epsilon)
                    ).astype(w.dtype) * w
        patch = mock.patch.object(nh, "gated_norm", ungrouped)
    elif fault == "gate_after_norm":
        inner = nh.gated_norm

        def after(y, z, w, c):
            normed = inner(y, jnp.full_like(z, 1.2784645), w, c)
            # silu(1.2784645) = 1: the norm alone, then the gate
            return (normed.astype(jnp.float32)
                    * jax.nn.silu(z.astype(jnp.float32))).astype(w.dtype)
        patch = mock.patch.object(nh, "gated_norm", after)
    elif fault == "rotary":
        patch = mock.patch.object(
            nh, "position_signal",
            lambda q, k, positions, c: (rope(q, positions, 10000.0),
                                        rope(k, positions, 10000.0)))
    elif fault == "relu_for_relu2":
        inner = moe._activate

        def relu(up, width, activation):
            if activation == "relu2":
                return jax.nn.relu(up)
            return inner(up, width, activation)
        patch = contextlib.ExitStack()
        patch.enter_context(mock.patch.object(moe, "_activate", relu))
        patch.enter_context(mock.patch.object(
            nh, "relu2_mlp", lambda up, down, h: _linear(
                down, jax.nn.relu(_linear(up, h)))))
    elif fault in ("top21", "no_scaling", "router_bf16"):
        patch = mock.patch.object(nh, "route", _route(fault))
    elif fault == "experts_next_share":
        patch = mock.patch.object(
            nh, "held_range",
            lambda c: (c.first_expert + c.experts_held, c.experts_held))
    elif fault == "shared_from_latent":
        patch = mock.patch.object(
            nh, "shared_input",
            lambda lp, h, latent: _linear(lp["latent_up"], latent))
    else:           # renorm_over_held
        inner = moe.grouped_ffn

        def grouped_ffn(x, groups_of, weights, *a, held=None, **k):
            first, count = held
            mine = (groups_of >= first) & (groups_of < first + count)
            kept = jnp.where(mine, weights, 0.0)
            total = weights.sum(-1, keepdims=True)
            weights = kept / (kept.sum(-1, keepdims=True) + 1e-20) * total
            return inner(x, groups_of, weights, *a, held=held, **k)
        patch = mock.patch.object(moe, "grouped_ffn", grouped_ffn)
    # the kernels' own jits remember what they traced, too
    serving._PAGED_STEP_CACHE.clear()
    jax.clear_caches()
    try:
        with patch:
            yield
    finally:
        serving._PAGED_STEP_CACHE.clear()
        jax.clear_caches()
