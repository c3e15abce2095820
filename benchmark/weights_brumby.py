"""The weights the ``brumby`` cells run on: seeded random bfloat16, drawn
HERE (the program's initialiser is not used: the reference must not be
fed what the code under test made of a seed) in the layout of
``bigdl_tpu.llm.models.brumby`` (its module docstring says which array
is which), on the device, each array waited for before the next is
drawn (the float32 draw of the embedding is 3.1 GB).

Conditioned as ``benchmark/weights_deepseek.py`` conditions Kanana's,
so that ``correct`` can tell right from wrong: every linear zero-mean
at unit gain (output rms = input rms for its fan-in), norms at 1, and
the projections BACK into the residual stream (``o_proj``,
``down_proj``) at the gain ``back`` the configuration file states
(``weights_back_gain``, and why). One thing is this family's own: the
**gate**. ``gamma = sigmoid(W_g u + b)`` with ``W_g`` drawn at the
spread the file states (``weights_gate_spread``: the standard deviation
of ``W_g u`` for a normed ``u``) around a bias a KV head drawn evenly in
``weights_gate_bias`` (3 .. 6), so that ``gamma`` lands in about 0.9 to
0.999: a state that remembers ten to a thousand tokens, as a trained
gate does. With a zero-mean gate ``gamma`` is 0.5, the state is the last
two tokens, and no comparison could tell a state that was lost from one
that was kept. Shapes, types and bytes, and so every kernel's time, do
not depend on any of it.
"""

from __future__ import annotations

import math


def seeded_bf16_params(cfg, seed: int, back: float, gate_spread: float,
                       gate_bias):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.brumby import qkv_widths
    dtype = jnp.bfloat16
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nq = cfg.num_attention_heads * d
    hkv = cfg.num_key_value_heads
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 8 * cfg.num_hidden_layers + 8))

    def mk(shape, fan_in, gain=1.0):
        x = jax.block_until_ready(
            jax.random.normal(next(keys), shape, jnp.float32)
            * (gain / math.sqrt(fan_in)))
        return jax.block_until_ready(x.astype(dtype))

    layers = []
    for _ in range(cfg.num_hidden_layers):
        layers.append({
            "qkv_proj": {"w": mk((sum(qkv_widths(cfg)), h), h)},
            "o_proj": {"w": mk((h, nq), nq, back)},
            "g_proj": {
                "w": jax.random.normal(next(keys), (hkv, h), jnp.float32)
                * (gate_spread / math.sqrt(h)),
                "b": jax.random.uniform(next(keys), (hkv,), jnp.float32,
                                        float(gate_bias[0]),
                                        float(gate_bias[1]))},
            "q_norm": jnp.ones((d,), dtype),
            "k_norm": jnp.ones((d,), dtype),
            "gate_up_proj": {"w": mk((2 * f, h), h)},
            "down_proj": {"w": mk((h, f), f, back)},
            "input_layernorm": jnp.ones((h,), dtype),
            "post_attention_layernorm": jnp.ones((h,), dtype)})
    return {"embed_tokens": mk((cfg.vocab_size, h), 1.0),
            "norm": jnp.ones((h,), dtype),
            "lm_head": {"w": mk((cfg.vocab_size, h), h)},
            "layers": layers}
