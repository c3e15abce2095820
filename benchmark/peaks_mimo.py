"""The least bytes a decode step of a ``mimo_v2`` configuration must
read from HBM, by part, from the configuration's sizes alone: the work,
whatever implements it. Decode at a few dozen rows is bound by HBM, so
these over the published rate (``peaks.py``) are the least time.

Weights are bfloat16, 2 bytes a parameter, as the checkpoint is
published and as the program holds them; a cached token of a layer is
``kv_heads x (head_dim + v_head_dim)`` bfloat16 numbers (4 x 320 on a
full layer, 8 x 320 on a window layer at the published widths: what has
to be read, not the 384 a row is held at). ``kind`` is 0 for a full
layer, 1 for a window layer, as in ``hybrid_layer_pattern``.
"""

BYTES = 2   # bfloat16


def attention_bytes(c, kind: int) -> int:
    """One layer's attention linears: the fused q | k | v and o."""
    nh, h = c.num_attention_heads, c.hidden_size
    hkv = c.kv_heads(kind)
    return BYTES * h * (nh * c.head_dim + hkv * c.head_dim
                        + hkv * c.v_head_dim + nh * c.v_head_dim)


def expert_bytes(c) -> int:
    """One routed expert: gate, up, down."""
    return BYTES * 3 * c.hidden_size * c.moe_intermediate_size


def router_bytes(c) -> int:
    """One layer's router weight, over ALL the experts, and its
    correction bias (float32)."""
    return BYTES * c.hidden_size * c.n_routed_experts \
        + 4 * c.n_routed_experts


def dense_ffn_bytes(c) -> int:
    return BYTES * 3 * c.hidden_size * c.intermediate_size


def head_bytes(c) -> int:
    """The output head; of the embedding a step reads a row a token."""
    return BYTES * c.vocab_size * c.hidden_size


def cached_token_bytes(c, kind: int) -> int:
    """One cached token of one layer of that kind."""
    return BYTES * c.kv_heads(kind) * (c.head_dim + c.v_head_dim)


def class_read_bytes(c, kind: int, ctx_tokens: float) -> float:
    """The cached rows the layers of one kind read for ``ctx_tokens``
    attended tokens (summed over rows and steps)."""
    return ctx_tokens * len(c.layers_of(kind)) * cached_token_bytes(c, kind)


def held_experts_bytes(c, experts_touched: float,
                       layer_steps: float) -> float:
    """Expert layers over some steps: the held experts that got a token
    (summed over layers and steps) and, per layer and step, the
    router."""
    return experts_touched * expert_bytes(c) + layer_steps * router_bytes(c)


def fixed_step_bytes(c) -> int:
    """What every decode step reads whatever its batch: each layer's
    attention linears, the dense layers' FFN, the head."""
    return sum(attention_bytes(c, k) for k in c.hybrid_layer_pattern) \
        + (c.num_hidden_layers - c.num_moe_layers) * dense_ffn_bytes(c) \
        + head_bytes(c)


def decode_steps_bytes(c, steps: float, experts_touched: float,
                       full_ctx_tokens: float,
                       window_ctx_tokens: float) -> float:
    """Everything ``steps`` decode steps must read at the least."""
    return steps * fixed_step_bytes(c) \
        + held_experts_bytes(c, experts_touched, steps * c.num_moe_layers) \
        + class_read_bytes(c, 0, full_ctx_tokens) \
        + class_read_bytes(c, 1, window_ctx_tokens)


def held_weight_bytes(c) -> int:
    """The weights this chip holds: the embedding, the head, every
    layer's attention and router, the dense FFNs, the held experts."""
    return 2 * head_bytes(c) + fixed_step_bytes(c) - head_bytes(c) \
        + c.num_moe_layers * (router_bytes(c)
                              + c.experts_held * expert_bytes(c))
