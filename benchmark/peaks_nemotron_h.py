"""The least bytes a decode step of a ``nemotron_h`` configuration must
move through HBM, by part, and the operations and bytes of its prefill's
chunked state-space kernel, from the configuration's sizes alone: the
work, whatever implements it. Decode at a few dozen rows is bound by
HBM, so the bytes over the published rate (``peaks.py``) are the least
time.

Weights are bfloat16, 2 bytes a parameter, as the checkpoint is
published and as the program holds them (the decays, the skip and the
convolution's taps and bias are float32: a few KB a layer). A Mamba-2
layer's state is float32 and is **read and written** at every token: a
live row moves ``H x P x N x 4`` bytes twice over a layer (4.19 MB one
way at 128 x 64 x 128); the convolution's window (61 KB) likewise. A
cached token of an attention layer is ``2 x kv_heads x head_dim``
bfloat16 numbers (1,024 B at 2 x 128). No lane padding is counted
anywhere: these are the bytes that have to move.
"""

BYTES = 2       # bfloat16 weights and cache
STATE_BYTES = 4  # float32 state


def mamba_layer_bytes(c) -> int:
    """One Mamba-2 layer's weights: ``in_proj``, ``out_proj``, the two
    norms (bfloat16); the convolution, the decays and the skip
    (float32)."""
    h = c.hidden_size
    return BYTES * (h * (2 * c.d_inner + 2 * c.n_groups * c.ssm_state_size
                         + c.mamba_num_heads) + c.d_inner * h
                    + h + c.d_inner) \
        + 4 * ((c.conv_kernel + 1) * c.conv_dim + 3 * c.mamba_num_heads)


def attention_layer_bytes(c) -> int:
    """One attention layer's weights: the fused q | k | v, o, its norm."""
    h, d = c.hidden_size, c.head_dim
    nh, hkv = c.num_attention_heads, c.num_key_value_heads
    return BYTES * (h * (nh + 2 * hkv) * d + nh * d * h + h)


def expert_bytes(c) -> int:
    """One routed expert: up and down, in the latent (not gated)."""
    return BYTES * 2 * c.moe_latent_size * c.moe_intermediate_size


def expert_layer_fixed_bytes(c) -> int:
    """What an expert layer reads whatever its tokens chose: the router
    over ALL the experts and its float32 correction bias, the latent
    down- and up-projection, the shared expert, its norm."""
    h = c.hidden_size
    return BYTES * (h * c.n_routed_experts + 2 * h * c.moe_latent_size
                    + 2 * h * c.moe_shared_expert_intermediate_size + h) \
        + 4 * c.n_routed_experts


def head_bytes(c) -> int:
    """The output head; of the embedding a step reads a row a token."""
    return BYTES * c.vocab_size * c.hidden_size


def state_bytes_a_row_layer(c) -> int:
    """One live row's state matrix of one Mamba-2 layer, one way."""
    return STATE_BYTES * c.mamba_num_heads * c.mamba_head_dim \
        * c.ssm_state_size


def window_bytes_a_row_layer(c) -> int:
    """The convolution's last inputs of one row and layer, one way."""
    return BYTES * (c.conv_kernel - 1) * c.conv_dim


def ssm_decode_bytes(c, rows: float) -> float:
    """What the decode kernel (``kernels.ssm.ssm_decode``) must move for
    ``rows`` live rows of ONE layer: the state, read and written (the
    engine's ``ssm_state_bytes_moved_total`` sums it over the layers
    and the steps)."""
    return 2 * rows * state_bytes_a_row_layer(c)


def cached_token_bytes(c) -> int:
    """One cached token of one attention layer: K and V."""
    return BYTES * 2 * c.num_key_value_heads * c.head_dim


def fixed_step_bytes(c) -> int:
    """What every decode step reads whatever its batch."""
    return len(c.layers_of("M")) * mamba_layer_bytes(c) \
        + len(c.layers_of("*")) * attention_layer_bytes(c) \
        + c.num_moe_layers * expert_layer_fixed_bytes(c) \
        + head_bytes(c) + BYTES * c.hidden_size


def decode_steps_bytes(c, steps: float, ssm_rows: float,
                       experts_touched: float,
                       kv_ctx_tokens: float) -> float:
    """Everything ``steps`` decode steps must move at the least:
    ``ssm_rows`` live rows in all (each moves every Mamba-2 layer's
    state and window both ways), ``experts_touched`` held experts with a
    token summed over layers and steps, ``kv_ctx_tokens`` cached tokens
    attended (by every attention layer)."""
    n_m = len(c.layers_of("M"))
    return steps * fixed_step_bytes(c) \
        + 2 * ssm_rows * n_m * (state_bytes_a_row_layer(c)
                                + window_bytes_a_row_layer(c)) \
        + experts_touched * expert_bytes(c) \
        + kv_ctx_tokens * len(c.layers_of("*")) * cached_token_bytes(c)


def held_weight_bytes(c) -> int:
    """The weights this chip holds: the embedding, the head, every
    layer's fixed part and the held experts."""
    return head_bytes(c) + fixed_step_bytes(c) \
        + c.num_moe_layers * c.experts_held * expert_bytes(c)


def state_held_bytes(c, slots: int) -> int:
    """The state class's arrays: ``slots`` rows and the trash row."""
    return (1 + slots) * len(c.layers_of("M")) * (
        state_bytes_a_row_layer(c) + window_bytes_a_row_layer(c))


def prefill_position_flops(c) -> int:
    """Multiply-adds times two of the chunked form, a position and
    layer, at whole sub-chunks of ``chunk_size``: a group's band ``C
    B^T`` once and a head's three products (the band with ``x``, ``C``
    with the state, ``x^T`` with ``B``)."""
    q, n, p = c.chunk_size, c.ssm_state_size, c.mamba_head_dim
    return 2 * (c.n_groups * q * n + c.mamba_num_heads * (q * p + 2 * p * n))


def prefill_position_bytes(c) -> int:
    """What the chunked kernel must read and write a position and
    layer: ``dt x`` twice (by rows and transposed), ``B``, ``C`` and the
    two layouts of the running sums in, ``y`` out, float32; the state
    itself moves once a chunk and is left out."""
    return 4 * (3 * c.d_inner + 2 * c.n_groups * c.ssm_state_size
                + 2 * c.mamba_num_heads)
