"""The weights GLM-5's cell runs on: seeded random bfloat16, drawn HERE
(not by the program's initialiser: the reference must not be fed what
the code under test made of a seed) in the layout of
``bigdl_tpu.llm.models.deepseek`` with its ``q_lora_rank`` and indexer
(that module's docstring says which array is which), on the device,
each stacked array a layer at a time.

Conditioned as ``weights_deepseek.py`` conditions Kanana's: every
linear zero-mean at unit gain (output rms = input rms for its fan-in),
the projections back into the residual stream (``o_proj``, the dense
``down_proj``, the experts' ``w_down``) at ``back`` (the configuration's
``weights_back_gain``), norms at 1, the indexer's key norm with weight 1
and bias 0, the routers' correction bias N(0, 0.05^2). The routers (the
router of every expert layer and its bias) are drawn from
``router_seed``, the same on every ``--seed`` (as MiMo's and Nemotron's
are): this chip holds 16 of 256 experts, and the share of the
assignments that lands here must not move with the seed. Shapes, types
and bytes, and so every kernel's time, do not depend on any of it.
"""

from __future__ import annotations

import math


def seeded_bf16_params(cfg, seed: int, back: float, router_seed: int):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.deepseek import indexer_shapes, linear_shapes
    dtype = jnp.bfloat16
    h, f, i = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    ld, lm, g = cfg.first_k_dense_replace, cfg.num_moe_layers, cfg.n_groups
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def mk(shape, fan_in, gain=1.0, key=None):
        def draw(k, shape):
            return (jax.random.normal(k, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        key = next(keys) if key is None else key
        if len(shape) < 3:
            return draw(key, shape)
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(key, shape[0]))

    def attn(n):
        out = {name: {"w": mk((n,) + s, s[1],
                              back if name == "o_proj" else 1.0)}
               for name, s in linear_shapes(cfg).items()}
        out["kv_a_layernorm"] = jnp.ones((n, cfg.kv_lora_rank), dtype)
        out["q_a_layernorm"] = jnp.ones((n, cfg.q_lora_rank), dtype)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            out[norm] = jnp.ones((n, h), dtype)
        out["indexer"] = {name: {"w": mk((n,) + s, s[1])}
                          for name, s in indexer_shapes(cfg).items()}
        out["indexer"]["k_norm"] = {
            "w": jnp.ones((n, cfg.index_head_dim), dtype),
            "b": jnp.zeros((n, cfg.index_head_dim), dtype)}
        return out

    dense = attn(ld)
    dense["gate_up_proj"] = {"w": mk((ld, 2 * f, h), h)}
    dense["down_proj"] = {"w": mk((ld, h, f), f, back)}
    layers = attn(lm)
    rk = jax.random.split(jax.random.PRNGKey(router_seed % (2 ** 31 - 1)))
    layers["router"] = {
        "w": mk((lm, cfg.n_routed_experts, h), h, key=rk[0]),
        "bias": 0.05 * jax.random.normal(
            rk[1], (lm, cfg.n_routed_experts), jnp.float32)}
    return jax.block_until_ready({
        "embed_tokens": mk((cfg.vocab_size, h), 1.0),
        "norm": jnp.ones((h,), dtype),
        "lm_head": {"w": mk((cfg.vocab_size, h), h)},
        "dense_layers": dense,
        "layers": layers,
        "experts": {"w_gate_up": mk((lm, g, h, 2 * i), h),
                    "w_down": mk((lm, g, i, h), i, back)},
    })
