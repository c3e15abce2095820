"""The window-class decode-attention kernel's share of its roofline
inside the decode program: the cached rows the slice's steps attend in
the class that keeps a window (the engine's ``window_ctx_tokens_total``,
the sum of min(length, window) over rows and steps, x its layers x 8
heads x 320 numbers x 2 bytes, ``peaks_mimo.class_read_bytes``) over the
published HBM rate, over the device time of
``window_attention_decode_stats``. The kernel fetches a request's whole
ring (256 positions held 384 wide for 128 attended at 320), so the
share cannot pass 128 x 320 / (256 x 384) = 42 %."""

from benchmark import peaks_mimo
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run, "window_attention_decode")
    if got is None or "window_ctx_tokens_total" not in got[1]:
        return None
    sec, c = got
    least = peaks_mimo.class_read_bytes(
        run["model"], 1, c["window_ctx_tokens_total"]) / hbm_rate(run)
    return 100.0 * least / sec
