"""The least bytes a decode step of a ``brumby`` configuration must move
through HBM and the operations of its prefill's retention kernel, from
the configuration's sizes alone: the work, whatever implements it.
Decode is bound by HBM, so the bytes over the published rate
(``peaks.py``) are the least time.

Weights are bfloat16, 2 bytes a parameter, as the checkpoint is
published and as the program holds them. The state is float32 and is
**read and written** at every token: a live row moves, a layer and KV
head, the state ``head_dim x state_width`` and the normaliser
``state_width`` twice over. ``state_width`` is 8,320 at a head of 128:
the 8,256 products ``x_a x_b``, ``a <= b``, in 65 whole tiles of 128
(64 zeros the layout pads), as ``kernels/retention.py`` holds them.
"""

BYTES = 2       # bfloat16 weights
STATE_BYTES = 4  # float32 state


def state_width(c) -> int:
    return c.head_dim * (c.head_dim // 2 + 1)


def layer_bytes(c) -> int:
    """One layer's weights: q, k, v, o, the gate, the SwiGLU, the four
    norms."""
    h, d = c.hidden_size, c.head_dim
    nh, hkv = c.num_attention_heads, c.num_key_value_heads
    return BYTES * (h * (nh + 2 * hkv) * d + nh * d * h
                    + 3 * h * c.intermediate_size + 2 * h + 2 * d) \
        + 4 * (hkv * h + hkv)


def head_bytes(c) -> int:
    """The output head; of the embedding a step reads a row a token."""
    return BYTES * c.vocab_size * c.hidden_size


def state_bytes_a_row_layer(c) -> int:
    """One live row's state and normaliser of one layer, one way."""
    return STATE_BYTES * c.num_key_value_heads * (c.head_dim + 1) \
        * state_width(c)


def state_bytes_moved(c, rows: float, layers: float = None) -> float:
    """What a decode step's retention kernels must move for ``rows``
    live rows: every layer's state, read and written."""
    layers = c.num_hidden_layers if layers is None else layers
    return 2 * rows * layers * state_bytes_a_row_layer(c)


def fixed_step_bytes(c) -> int:
    """What every decode step reads whatever its batch."""
    return c.num_hidden_layers * layer_bytes(c) + head_bytes(c)


def decode_steps_bytes(c, steps: float, state_rows: float) -> float:
    """Everything ``steps`` decode steps that advance ``state_rows``
    rows in all must move at the least."""
    return steps * fixed_step_bytes(c) + state_bytes_moved(c, state_rows)


def held_weight_bytes(c) -> int:
    return 2 * head_bytes(c) + c.num_hidden_layers * layer_bytes(c) \
        + BYTES * c.hidden_size


def state_held_bytes(c, slots: int) -> int:
    """The state arrays the engine builds: ``slots`` rows and the trash
    row, every layer."""
    return (1 + slots) * c.num_hidden_layers * state_bytes_a_row_layer(c)


def prefill_chunk_flops(c, chunk: int, sub: int = 256) -> int:
    """Multiply-adds times two of the retention kernel over one chunk of
    ``chunk`` positions of one layer: inside each sub-chunk of ``sub``
    the band ``(q . k)`` and its product with the values; the state
    read out for every query (``state_width x head_dim`` a query head
    and position); the keys folded into the state."""
    sub = min(sub, chunk)
    d, grp = c.head_dim, c.num_attention_heads // c.num_key_value_heads
    band = grp * chunk * sub * 2 * d
    read = grp * chunk * state_width(c) * d
    fold = chunk * state_width(c) * d
    return 2 * c.num_key_value_heads * (band + read + fold)
