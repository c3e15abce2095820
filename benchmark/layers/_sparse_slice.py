"""What the readers of the ``glm_moe_dsa`` cell share: the decode
programs' device seconds in the ops a name picks, with the slice's
counters (``_decode_slice``), and the selection's ops. A name is a
kernel's jitted function (``index_scores_decode``,
``sparse_latent_attention``); the selection (``dsa_select``, a
``lax.top_k``) is compiled into a ``sort`` of the row's whole score
array, ``sort[<rows>x<positions>]``, the only sort whose second
dimension reaches ``index_topk`` (the routers sort 256 scores a row,
the expert dispatch one flat array)."""

from benchmark.layers._decode_slice import decode_slice


def dsa_slice(run, op_substring=None):
    """``decode_slice`` where the model selects (None elsewhere: a
    configuration without ``index_topk`` or a parent without the
    counters)."""
    m = run.get("model")
    if not getattr(m, "index_topk", 0):
        return None
    got = decode_slice(run, op_substring)
    if got is None or "index_key_bytes_total" not in got[1]:
        return None
    return got


def is_selection(op: str, topk: int) -> bool:
    """``<program>:sort[AxB]`` with ``B >= topk``."""
    name = op.split(":", 1)[-1]
    if not name.startswith("sort["):
        return False
    dims = name[5:].rstrip("]").split("x")
    return len(dims) == 2 and dims[1].isdigit() and int(dims[1]) >= topk
