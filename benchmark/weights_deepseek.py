"""The weights the ``deepseek_v3`` cells run on: seeded random bfloat16,
drawn HERE (the program's initialiser is not used: the reference must
not be fed what the code under test made of a seed) in the layout of
``bigdl_tpu.llm.models.deepseek`` (its module docstring says which array
is which), on the device, each stacked array a layer at a time, so the
float32 draw of all the experts (11 GB) never exists.

Conditioned as ``benchmark/weights.py`` conditions Mistral's, so that
``correct`` can tell right from wrong: every linear zero-mean at unit
gain (output rms = input rms for its fan-in), attention soft, the
router's sigmoid scores spread around 0.5, its correction bias N(0,
0.05^2) (enough to change which experts are chosen, as a trained bias
does), norms at 1. One thing more, because routing is discrete: the
projections BACK into the residual stream (``o_proj``, the dense
``down_proj``, the experts' ``w_down``) carry the gain ``back`` the
configuration file states (``weights_back_gain``, with the readings it
was chosen from). At gain 1 one expert chosen differently moves a
token's stream by 0.4 of its size, the next router follows, and
rounding alone carries a bfloat16 forward 2.4 logit sigmas from the
float32 one in 8 layers; at a gain near 0 the layers say nothing and no
fault in them can be seen (PERF.md section 6, PR 27). Shapes, types and
bytes, and so every kernel's time, do not depend on it.
"""

from __future__ import annotations

import math


def seeded_bf16_params(cfg, seed: int, back: float):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.deepseek import linear_shapes
    dtype = jnp.bfloat16
    h, f, i = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    ld, lm, g = cfg.first_k_dense_replace, cfg.num_moe_layers, cfg.n_groups
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def mk(shape, fan_in, gain=1.0):
        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        if len(shape) < 3:
            return draw(next(keys), shape)
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(next(keys), shape[0]))

    def attn(n):
        out = {name: {"w": mk((n,) + s, s[1],
                              back if name == "o_proj" else 1.0)}
               for name, s in linear_shapes(cfg).items()}
        out["kv_a_layernorm"] = jnp.ones((n, cfg.kv_lora_rank), dtype)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            out[norm] = jnp.ones((n, h), dtype)
        return out

    dense = attn(ld)
    dense["gate_up_proj"] = {"w": mk((ld, 2 * f, h), h)}
    dense["down_proj"] = {"w": mk((ld, h, f), f, back)}
    layers = attn(lm)
    layers["router"] = {
        "w": mk((lm, cfg.n_routed_experts, h), h),
        "bias": 0.05 * jax.random.normal(
            next(keys), (lm, cfg.n_routed_experts), jnp.float32)}
    return jax.block_until_ready({
        "embed_tokens": mk((cfg.vocab_size, h), 1.0),
        "norm": jnp.ones((h,), dtype),
        "lm_head": {"w": mk((cfg.vocab_size, h), h)},
        "dense_layers": dense,
        "layers": layers,
        "experts": {"w_gate_up": mk((lm, g, h, 2 * i), h),
                    "w_down": mk((lm, g, i, h), i, back)},
    })
