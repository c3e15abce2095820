"""The least bytes a decode step of a ``deepseek_v3`` configuration must
read from HBM, by part, from the configuration's sizes alone: the work,
whatever implements it. Decode at a few dozen rows is bound by HBM, so
these over the published rate (``peaks.py``) are the least time.

Weights are bfloat16, 2 bytes a parameter, as the checkpoint is
published and as the program holds them; a cached latent row is
``kv_lora_rank + qk_rope_head_dim`` bfloat16 numbers (576 at the
published widths: what has to be read, not the 640 the pool is held
at).
"""

BYTES = 2   # bfloat16


def mla_bytes(c) -> int:
    """One layer's attention linears: q, kv_a, kv_b, o."""
    nh, h = c.num_attention_heads, c.hidden_size
    return BYTES * (
        h * nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)
        + h * (c.kv_lora_rank + c.qk_rope_head_dim)
        + c.kv_lora_rank * nh * (c.qk_nope_head_dim + c.v_head_dim)
        + nh * c.v_head_dim * h)


def expert_bytes(c) -> int:
    """One routed expert: gate, up, down."""
    return BYTES * 3 * c.hidden_size * c.moe_intermediate_size


def shared_expert_bytes(c) -> int:
    """One layer's shared expert, a SwiGLU of width n_shared x I."""
    return c.n_shared_experts * expert_bytes(c)


def router_bytes(c) -> int:
    """One layer's router weight and its correction bias (float32)."""
    return BYTES * c.hidden_size * c.n_routed_experts \
        + 4 * c.n_routed_experts


def dense_ffn_bytes(c) -> int:
    return BYTES * 3 * c.hidden_size * c.intermediate_size


def head_bytes(c) -> int:
    """The output head; of the embedding a step reads a row a token."""
    return BYTES * c.vocab_size * c.hidden_size


def latent_row_bytes(c) -> int:
    """One cached token of one layer."""
    return BYTES * (c.kv_lora_rank + c.qk_rope_head_dim)


def moe_ffn_bytes(c, experts_touched: float, layer_steps: float) -> float:
    """Expert layers over some steps: the routed experts that got a
    token (summed over layers and steps) and, per layer and step, the
    shared expert and the router."""
    return experts_touched * expert_bytes(c) \
        + layer_steps * (shared_expert_bytes(c) + router_bytes(c))


def latent_read_bytes(c, ctx_tokens: float) -> float:
    """The cached rows all layers read for ``ctx_tokens`` attended
    tokens (summed over rows and steps)."""
    return ctx_tokens * c.num_hidden_layers * latent_row_bytes(c)


def decode_steps_bytes(c, steps: float, experts_touched: float,
                       ctx_tokens: float) -> float:
    """Everything ``steps`` decode steps must read at the least."""
    n_moe = c.num_hidden_layers - c.first_k_dense_replace
    fixed = c.num_hidden_layers * mla_bytes(c) \
        + c.first_k_dense_replace * dense_ffn_bytes(c) + head_bytes(c)
    return steps * fixed + moe_ffn_bytes(c, experts_touched, steps * n_moe) \
        + latent_read_bytes(c, ctx_tokens)
