"""The weights the ``nemotron_h`` cells run on: seeded random bfloat16,
drawn HERE (the program's initialiser is not used: the reference must
not be fed what the code under test made of a seed) in the layout of
``bigdl_tpu.llm.models.nemotron_h`` (its module docstring says which
array is which), on the device, an expert at a time, each array waited
for before the next is drawn.

Conditioned as ``benchmark/weights_mimo.py`` conditions MiMo's, so that
``correct`` can tell right from wrong: every linear zero-mean at unit
gain (output rms = input rms for its fan-in), attention soft, the
router's sigmoid scores spread around 0.5, its correction bias N(0,
0.05^2), norms at 1, and the projections BACK into the residual stream
(``out_proj``, ``o_proj``, ``latent_up``, the shared expert's down) at
the gain ``back`` the configuration file states (``weights_back_gain``,
and why). The experts' own down-projection stays at unit gain: it ends
in the latent, and ``latent_up`` brings the sum back.

Two things are this family's own. **The decays**: ``A`` evenly in 1 ..
16 a head (the family's initialiser) and ``dt_bias`` such that ``dt A``
at a zero projection is log-evenly in ``weights_decay`` (0.001 .. 0.1),
the time steps' rows of ``in_proj`` at the spread the file states
(``weights_dt_spread``: the standard deviation of the projection for a
normed input), so that ``exp(dt a)`` lands in about 0.9 .. 0.999 a
step: a state that remembers ten to a thousand tokens, as a trained
one does. With ``dt`` of order 1 the state is the last token and no
comparison could tell a state that was lost from one that was kept.
``D`` is N(1, 0.5^2) a head and the convolution's bias N(0, 0.5^2) a
channel, so that leaving either out shows. **The routers are drawn from
a seed of the configuration's** (``weights_router_seed``), not from
``--seed``, as MiMo's are: this chip holds 128 of 512 experts, a step
costs 11 MB an expert touched, and the share of the assignments that
falls here must be near 1/4 on every ``--seed`` for the step's cost to
be the same. Shapes, types and bytes, and so every kernel's time, do not
depend on any of it.
"""

from __future__ import annotations

import math

from benchmark.weights_mimo import router_params


def seeded_bf16_params(cfg, seed: int, back: float, dt_spread: float,
                       decay, router_seed: int):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.nemotron_h import (in_proj_widths,
                                                 qkv_widths)
    dtype = jnp.bfloat16
    h = cfg.hidden_size
    lat, i = cfg.moe_latent_size, cfg.moe_intermediate_size
    si, taps = cfg.moe_shared_expert_intermediate_size, cfg.conv_kernel
    nq = qkv_widths(cfg)[0]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 12 * cfg.num_hidden_layers + 8))

    def mk(shape, fan_in, gain=1.0):
        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        # each step is waited for before the next is dispatched (the
        # host runs ahead of the device otherwise and the float32 draw,
        # its scaled copy and its bfloat16 copy are all held at once)
        if len(shape) < 3:
            x = jax.block_until_ready(
                jax.random.normal(next(keys), shape, jnp.float32)
                * (gain / math.sqrt(fan_in)))
            return jax.block_until_ready(x.astype(dtype))
        return jax.block_until_ready(jax.lax.map(
            lambda k: draw(k, shape[1:]),
            jax.random.split(next(keys), shape[0])))

    def f32(shape, mean=0.0, spread=1.0):
        return mean + spread * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

    layers = []
    for l, kind in enumerate(cfg.hybrid_override_pattern):
        lp = {"norm": jnp.ones((h,), dtype)}
        if kind == "M":
            nz, nc, nh = in_proj_widths(cfg)
            heads = cfg.mamba_num_heads
            a = jax.random.uniform(next(keys), (heads,), jnp.float32,
                                   1.0, 16.0)
            dt0 = jnp.exp(jax.random.uniform(
                next(keys), (heads,), jnp.float32,
                math.log(float(decay[0])), math.log(float(decay[1])))) / a
            lp.update({
                "in_proj": {"w": jnp.concatenate(
                    [mk((nz + nc, h), h), mk((nh, h), h, dt_spread)])},
                "conv_w": f32((taps, cfg.conv_dim),
                              spread=1.0 / math.sqrt(taps)),
                "conv_b": f32((cfg.conv_dim,), spread=0.5),
                "A_log": jnp.log(a),
                "dt_bias": jnp.log(jnp.expm1(dt0)),
                "D": f32((heads,), 1.0, 0.5),
                "gate_norm": jnp.ones((cfg.d_inner,), dtype),
                "out_proj": {"w": mk((h, cfg.d_inner), cfg.d_inner, back)}})
        elif kind == "*":
            lp.update({"qkv_proj": {"w": mk((sum(qkv_widths(cfg)), h), h)},
                       "o_proj": {"w": mk((h, nq), nq, back)}})
        else:
            lp.update({
                "router": router_params(cfg, router_seed, l),
                "latent_down": {"w": mk((lat, h), h)},
                "latent_up": {"w": mk((h, lat), lat, back)},
                "shared_up": {"w": mk((si, h), h)},
                "shared_down": {"w": mk((h, si), si, back)},
                "experts": {"w_up": mk((cfg.experts_held, lat, i), lat),
                            "w_down": mk((cfg.experts_held, i, lat), i)}})
        layers.append(lp)
    return {"embed_tokens": mk((cfg.vocab_size, h), 1.0),
            "norm": jnp.ones((h,), dtype),
            "lm_head": {"w": mk((cfg.vocab_size, h), h)},
            "layers": layers}
