"""The latent decode-attention kernel's share of its roofline inside the
decode program: the cached rows the slice's steps attend (the engine's
``latent_ctx_tokens_total`` x layers x 576 x 2 bytes,
``peaks_deepseek.latent_read_bytes``) over the published HBM rate, over
the device time of ``latent_attention_decode_stats``."""

from benchmark import peaks_deepseek
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run, "latent_attention_decode_stats")
    if got is None:
        return None
    sec, c = got
    least = peaks_deepseek.latent_read_bytes(
        run["model"], c["latent_ctx_tokens_total"]) / hbm_rate(run)
    return 100.0 * least / sec
