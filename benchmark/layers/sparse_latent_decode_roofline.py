"""The sparse latent attention's share of its roofline inside the decode
program: the latent rows the slice's steps selected (the engine's
``sparse_latent_bytes_total``: min(len + 1, index_topk) x layers x 576 x
2 bytes) over the published HBM rate, over the device time of
``sparse_latent_attention``. The kernel reads the rows an XLA gather
laid out (640 wide, lane-padded: the share cannot pass 90 %); the
gather itself runs in a fusion the trace cannot name and is in the
bytes, not in the time."""

from benchmark.layers._decode_slice import hbm_rate
from benchmark.layers._sparse_slice import dsa_slice


def read(run, name):
    got = dsa_slice(run, "sparse_latent_attention")
    if got is None:
        return None
    sec, c = got
    return 100.0 * c["sparse_latent_bytes_total"] / hbm_rate(run) / sec
