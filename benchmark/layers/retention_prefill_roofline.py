"""The retention prefill kernel's share of the MXU's peak inside the
prefill programs: the operations of the positions the slice's chunks
computed (the engine's ``prefill_state_positions_total``: chunks x
layers x the chunk's length, a prompt's last chunk whole, a bucket
shorter than a chunk at its own length; ``peaks_brumby.
prefill_chunk_flops`` of a whole chunk a position: a chunk under 256
positions has a shorter band, 4 % of the operations, so such a chunk is
counted up to 2 % high; the cell has none) over the published bfloat16
rate, over the device time of the ``retention_prefill_chunk`` kernel."""

from benchmark import peaks_brumby
from benchmark.layers._retention_slice import peak, retention_slice


def read(run, name):
    got = retention_slice(run, "prefill", "retention_prefill_chunk")
    if got is None or not got[1].get("prefill_state_positions_total"):
        return None
    sec, c = got
    m = run["model"]
    flops = c["prefill_state_positions_total"] / m.prefill_chunk \
        * peaks_brumby.prefill_chunk_flops(m, m.prefill_chunk)
    return 100.0 * flops / peak(run, "bf16_flops_per_s") / sec
