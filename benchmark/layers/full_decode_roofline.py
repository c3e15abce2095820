"""The full-class decode-attention kernel's share of its roofline inside
the decode program: the cached rows the slice's steps attend in the
class that keeps every token (the engine's ``full_ctx_tokens_total`` x
its layers x 4 heads x 320 numbers x 2 bytes,
``peaks_mimo.class_read_bytes``: the 64 pad columns a row is held with
are not in the bytes) over the published HBM rate, over the device time
of ``full_attention_decode_stats``."""

from benchmark import peaks_mimo
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run, "full_attention_decode")
    if got is None or "full_ctx_tokens_total" not in got[1]:
        return None
    sec, c = got
    least = peaks_mimo.class_read_bytes(
        run["model"], 0, c["full_ctx_tokens_total"]) / hbm_rate(run)
    return 100.0 * least / sec
