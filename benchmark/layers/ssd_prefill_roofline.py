"""The chunked state-space prefill kernel's share of its roofline inside
the prefill programs: the positions the slice's chunks computed (the
engine's ``prefill_ssm_positions_total``: chunks x Mamba-2 layers x the
chunk's length, a prompt's last chunk whole) times what a position
costs (``peaks_nemotron_h.prefill_position_flops`` over the published
bfloat16 rate, or ``prefill_position_bytes`` over the HBM rate,
whichever is the longer: 6.55 MFLOP are 33 ns at the MXU's peak and the
107.5 KB of float32 operands 131 ns at the HBM's, so the bytes bound
it), over
the device time of the ``ssd_prefill_chunk`` kernel. A bucket shorter
than a sub-chunk of 128 runs the XLA twin: its positions are counted
and no kernel time is, which reads high; the cell's shortest prompts
(64 to 127 tokens, a twentieth of them) are a hundredth of its
positions."""

from benchmark import peaks_nemotron_h
from benchmark.layers._ssm_slice import peak, ssm_slice


def read(run, name):
    got = ssm_slice(run, "prefill", "ssd_prefill_chunk")
    if got is None or not got[1].get("prefill_ssm_positions_total"):
        return None
    sec, c = got
    m = run["model"]
    least = max(
        peaks_nemotron_h.prefill_position_flops(m)
        / peak(run, "bf16_flops_per_s"),
        peaks_nemotron_h.prefill_position_bytes(m)
        / peak(run, "hbm_bytes_per_s"))
    return 100.0 * c["prefill_ssm_positions_total"] * least / sec
