"""The weights the serving cells run on: the program's seeded sym_int4
parameters with their scales signed and set to unit gain, so that the
model is a well-conditioned function and ``correct`` can mean something.

``synthetic_q4_params`` draws uniform nibbles (0..15, stored weight + 8,
so every weight has mean -0.5 scale) and small *positive* scales. Every
linear then adds the same multiple of sum(x) to all its outputs: on the
chip the hidden state of the 7B model collapsed to rank one (singular
values 2.4e8 against 1.9e5, rms 1e4 to 1e6), the model emitted two
tokens, the argmax and the argmin of one logit row, and which of the two
came was a coin that the float32 reference, the dense forward and the
paged path tossed differently at a few positions in a hundred (PR 24:
8 of 20 seeds missed the reference by 8 sigma somewhere in 48 tokens).
Real q4_0 scales are signed. Here each scale gets a seeded random sign,
which takes the mean away, and every linear is scaled to unit gain
(output rms = input rms for a fan-in of K), which keeps attention soft
and the residual stream near sqrt(layers). Shapes, types, bytes and so
every kernel's time are untouched.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp

QK = 32                       # q4_0 group: one scale per 32 consecutive k
NIBBLE_RMS = math.sqrt(21.5)  # rms of (q - 8), q uniform on 0..15


def _is_linear(node) -> bool:
    return isinstance(node, dict) and "scale" in node


@functools.partial(jax.jit, donate_argnums=0)
def _condition(params, key):
    count = itertools.count()

    def fix(node):
        if not _is_linear(node):
            return node
        s = node["scale"]                   # (..., K / QK, N)
        sign = jax.random.rademacher(
            jax.random.fold_in(key, next(count)), s.shape, s.dtype)
        gain = NIBBLE_RMS * jnp.sqrt(jnp.mean(s * s)) \
            * math.sqrt(s.shape[-2] * QK)
        return {**node, "scale": s * sign / gain}
    return jax.tree_util.tree_map(fix, params, is_leaf=_is_linear)


def conditioned_q4_params(cfg, seed: int):
    """``synthetic_q4_params(cfg, seed)`` with signed unit-gain scales,
    made on the device from the seed."""
    from bigdl_tpu.llm.models.llama import synthetic_q4_params
    return _condition(synthetic_q4_params(cfg, seed=seed),
                      jax.random.PRNGKey(seed ^ 0x5CA1E))
