"""The retention decode kernel's share of its roofline inside the decode
program: the bytes of state the slice's steps asked it to move (the
engine's ``state_bytes_moved_total``: a live row's state and normaliser
of every layer, READ AND WRITTEN, at the 8,320 numbers a head's row is
held at) over the published HBM rate, over the device time of the
``retention_decode`` kernel. The normaliser's update and the query's
``phi`` run in XLA fusions beside the kernel: the normaliser's 0.8 % of
the bytes is in the numerator and not in the time."""

from benchmark.layers._retention_slice import peak, retention_slice


def read(run, name):
    got = retention_slice(run, "decode", "retention_decode")
    if got is None or not got[1].get("state_bytes_moved_total"):
        return None
    sec, c = got
    return 100.0 * c["state_bytes_moved_total"] \
        / peak(run, "hbm_bytes_per_s") / sec
