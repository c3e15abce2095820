"""The indexer's scoring kernel's share of its roofline inside the
decode program: the cached index keys the slice's steps scored (the
engine's ``index_key_bytes_total``: cached tokens x layers x 128 x 2
bytes) over the published HBM rate, over the device time of
``index_scores_decode``. The kernel walks a row's blocks of 512 keys
whole, so a row's last block reads past its length (at most 511 keys a
row and layer)."""

from benchmark.layers._decode_slice import hbm_rate
from benchmark.layers._sparse_slice import dsa_slice


def read(run, name):
    got = dsa_slice(run, "index_scores_decode")
    if got is None:
        return None
    sec, c = got
    return 100.0 * c["index_key_bytes_total"] / hbm_rate(run) / sec
