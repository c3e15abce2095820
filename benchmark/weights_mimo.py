"""The weights the ``mimo_v2`` cells run on: seeded random bfloat16,
drawn HERE (the program's initialiser is not used: the reference must
not be fed what the code under test made of a seed) in the layout of
``bigdl_tpu.llm.models.mimo`` (its module docstring says which array is
which), on the device, an expert at a time, so the float32 draw of a
layer's experts (1.6 GB) never exists.

Conditioned as ``benchmark/weights_deepseek.py`` conditions Kanana's,
so that ``correct`` can tell right from wrong: every linear zero-mean
at unit gain (output rms = input rms for its fan-in), attention soft
(scores of unit spread), the router's sigmoid scores spread around 0.5,
its correction bias N(0, 0.05^2), norms at 1, and the projections BACK
into the residual stream (``o_proj``, the dense ``down_proj``, the
experts' ``w_down``) at the gain ``back`` the configuration file states
(``weights_back_gain``, and why). One thing is this family's own: the
**sink** scalars are N(``sink_mean``, 1) with the mean the file states
(4): 128 keys of unit-spread scores put ``128 e^0.5 = 211`` in the
softmax's denominator, so a sink of N(0, 1) is half a percent of it and
no comparison could tell a program that leaves the sink out from one
that has it; at 4 it takes about a fifth of a window's mass, as a
trained sink takes a real share. Shapes, types and bytes, and so every
kernel's time, do not depend on either.

**The routers are drawn from a seed of the configuration's**
(``weights_router_seed``), not from ``--seed``, as the order of
arrivals is fixed by the traffic file: this chip holds 16 of 256
experts, so the share of the assignments that fall on it is a small
sample of the router's preferences (the mean of 16 correction biases of
spread 0.05 moves an expert's chances by a tenth), a step costs 50 MB
an expert touched, and with the routers drawn from ``--seed`` six seeds
put 5.3 to 6.9 % of the assignments here (6.25 % expected), 3.8 to 4.75
experts touched a layer, and ``itl_p95_ms`` spread 6 % (PERF.md §6,
PR 31). ``--seed`` draws every other weight and every token id.
"""

from __future__ import annotations

import math


def router_params(cfg, router_seed: int, layer: int):
    """One expert layer's router, float32 bias, from the
    configuration's own seed and the layer's number."""
    import jax
    import jax.numpy as jnp
    kw, kb = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(router_seed), layer))
    h = cfg.hidden_size
    return {"w": (jax.random.normal(kw, (cfg.n_routed_experts, h),
                                    jnp.float32) / math.sqrt(h))
            .astype(jnp.bfloat16),
            "bias": 0.05 * jax.random.normal(
                kb, (cfg.n_routed_experts,), jnp.float32)}


def seeded_bf16_params(cfg, seed: int, back: float, sink_mean: float,
                       router_seed: int):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.mimo import qkv_widths
    dtype = jnp.bfloat16
    h, i = cfg.hidden_size, cfg.moe_intermediate_size
    out_w = cfg.num_attention_heads * cfg.v_head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 8 * cfg.num_hidden_layers + 8))

    def mk(shape, fan_in, gain=1.0):
        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        # each step is waited for before the next is dispatched: the
        # host runs ahead of the device otherwise, and the float32 draw
        # of the embedding or the head (2.5 GB, drawn whole so that a
        # seed's weights stay what they were), its scaled copy and its
        # bfloat16 copy are all held at once, the head's beside what is
        # left of the embedding's: 15.3 GB at the peak, 14.1 with the
        # arrays waited for, 12.8 with the steps
        if len(shape) < 3:
            x = jax.block_until_ready(
                jax.random.normal(next(keys), shape, jnp.float32)
                * (gain / math.sqrt(fan_in)))
            return jax.block_until_ready(x.astype(dtype))
        return jax.block_until_ready(jax.lax.map(
            lambda k: draw(k, shape[1:]),
            jax.random.split(next(keys), shape[0])))

    layers = []
    for l, (kind, sparse) in enumerate(zip(cfg.hybrid_layer_pattern,
                                           cfg.moe_layer_freq)):
        lp = {"qkv_proj": {"w": mk((sum(qkv_widths(cfg, kind)), h), h)},
              "o_proj": {"w": mk((h, out_w), out_w, back)},
              "input_layernorm": jnp.ones((h,), dtype),
              "post_attention_layernorm": jnp.ones((h,), dtype)}
        if cfg.has_sink(kind):
            lp["sink"] = sink_mean + jax.random.normal(
                next(keys), (cfg.num_attention_heads,), jnp.float32)
        if sparse:
            lp["router"] = router_params(cfg, router_seed, l)
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
