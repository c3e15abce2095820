"""The whole decode step's share of its roofline, for a model whose
cache is a state a row: the least bytes the slice's decode steps must
move (every layer's weights and the head once a step, every live row's
state read and written: ``peaks_brumby.decode_steps_bytes``) over the
published HBM rate, over the decode program's device time. It reads the
same whatever implements the step."""

from benchmark import peaks_brumby
from benchmark.layers._retention_slice import peak, retention_slice


def read(run, name):
    got = retention_slice(run, "decode")
    if got is None or "state_rows_total" not in got[1]:
        return None
    sec, c = got
    m = run["model"]
    least = peaks_brumby.decode_steps_bytes(
        m, c["state_layer_steps_total"] / m.num_hidden_layers,
        c["state_rows_total"]) / peak(run, "hbm_bytes_per_s")
    return 100.0 * least / sec
