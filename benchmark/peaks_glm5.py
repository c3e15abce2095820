"""The least bytes a decode step of a ``glm_moe_dsa`` configuration
(GLM-5) must move, by part, and the bytes of each new kernel's work,
from the configuration's sizes and the engine's counters alone: the
work, whatever implements it. Decode at a few dozen rows is bound by
HBM, so these over the published rate (``peaks.py``) are the least
time.

Weights are bfloat16, 2 bytes a parameter, as the checkpoint is
published and as the program holds them. A cached index key is
``index_head_dim`` bfloat16 numbers (128); a selected latent row is
``kv_lora_rank + qk_rope_head_dim`` (576: what has to be read, not the
640 the pool is held at). The engine counts, when a step drains
(``deepseek.host_step_stats``): ``index_key_bytes_total`` = cached keys
x layers x 256 B (what the scoring kernel must read) and
``sparse_latent_bytes_total`` = min(len + 1, index_topk) x layers x
1,152 B (the selected rows).
"""

BYTES = 2   # bfloat16


def mla_bytes(c) -> int:
    """One layer's attention linears: q_a, q_b, kv_a, kv_b, o."""
    nh, h = c.num_attention_heads, c.hidden_size
    return BYTES * (
        h * c.q_lora_rank + c.q_lora_rank * nh * c.qk_head_dim
        + h * (c.kv_lora_rank + c.qk_rope_head_dim)
        + c.kv_lora_rank * nh * (c.qk_nope_head_dim + c.v_head_dim)
        + nh * c.v_head_dim * h)


def indexer_bytes(c) -> int:
    """One layer's indexer linears: W_qI, W_kI, W_w."""
    hi, di = c.index_n_heads, c.index_head_dim
    return BYTES * (c.q_lora_rank * hi * di + c.hidden_size * di
                    + c.hidden_size * hi)


def expert_bytes(c) -> int:
    """One routed expert: gate, up, down."""
    return BYTES * 3 * c.hidden_size * c.moe_intermediate_size


def shared_expert_bytes(c) -> int:
    return c.n_shared_experts * expert_bytes(c)


def router_bytes(c) -> int:
    """One layer's router over all the routed experts (held here or
    not) and its correction bias (float32)."""
    return BYTES * c.hidden_size * c.n_routed_experts \
        + 4 * c.n_routed_experts


def dense_ffn_bytes(c) -> int:
    return BYTES * 3 * c.hidden_size * c.intermediate_size


def head_bytes(c) -> int:
    """The output head over the vocabulary held here."""
    return BYTES * c.vocab_size * c.hidden_size


def fixed_step_bytes(c) -> int:
    """What every decode step reads whatever its rows: every layer's
    attention and indexer, the dense layers' FFN, each expert layer's
    router and shared expert, the head."""
    n_moe = c.num_hidden_layers - c.first_k_dense_replace
    return (c.num_hidden_layers * (mla_bytes(c) + indexer_bytes(c))
            + c.first_k_dense_replace * dense_ffn_bytes(c)
            + n_moe * (router_bytes(c) + shared_expert_bytes(c))
            + head_bytes(c))


def decode_steps_bytes(c, steps: float, experts_touched: float,
                       index_key_bytes: float,
                       sparse_latent_bytes: float) -> float:
    """Everything ``steps`` decode steps must move at the least: the
    fixed weights a step, the routed experts that got a token (summed
    over layers and steps), the cached index keys scored and the latent
    rows selected (the engine's two byte counters)."""
    return steps * fixed_step_bytes(c) + experts_touched * expert_bytes(c) \
        + index_key_bytes + sparse_latent_bytes


def weight_bytes(c) -> int:
    """The weights this chip holds: every layer's attention and indexer
    (and norms), the dense FFN, each expert layer's router, held routed
    experts and shared expert, the embedding and the head."""
    n_moe = c.num_hidden_layers - c.first_k_dense_replace
    norms = BYTES * (2 * c.hidden_size + c.q_lora_rank + c.kv_lora_rank
                     + 2 * c.index_head_dim)
    return (c.num_hidden_layers * (mla_bytes(c) + indexer_bytes(c) + norms)
            + c.first_k_dense_replace * dense_ffn_bytes(c)
            + n_moe * (router_bytes(c) + (c.routed_held
                                          + c.n_shared_experts)
                       * expert_bytes(c))
            + 2 * head_bytes(c) + BYTES * c.hidden_size)


def token_bytes(c) -> int:
    """Cache a token costs: a latent row held lane-padded plus an index
    key, every layer."""
    return c.num_hidden_layers * BYTES * (c.latent_width + c.index_head_dim)
