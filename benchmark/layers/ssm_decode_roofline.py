"""The state-space decode kernel's share of its roofline inside the
decode program: the bytes of state the slice's steps asked it to move
(the engine's ``ssm_state_bytes_moved_total``: a live row's float32
state of every Mamba-2 layer, READ AND WRITTEN) over the published HBM
rate, over the device time of the ``ssm_decode`` kernel. The
convolution's window and the small operands (``dt x``, ``B``, ``C``, a
row: 1 % of the state) move in XLA fusions beside the kernel and are in
neither the bytes nor the time."""

from benchmark.layers._ssm_slice import peak, ssm_slice


def read(run, name):
    got = ssm_slice(run, "decode", "ssm_decode")
    if got is None or not got[1].get("ssm_state_bytes_moved_total"):
        return None
    sec, c = got
    return 100.0 * c["ssm_state_bytes_moved_total"] \
        / peak(run, "hbm_bytes_per_s") / sec
