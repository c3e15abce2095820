"""The latent experts' product's share of its roofline inside the decode
program: the bytes the slice's expert layers must read of the held
experts that got a token (the engine's ``moe_experts_touched_total``
times ``peaks_nemotron_h.expert_bytes``, 11.0 MB: up and down, in the
latent) over the published HBM rate, over the device time of the
``moe_expert_ffn`` kernel. The router, the latent projections and the
shared expert run in XLA fusions beside the kernel and are in neither
the bytes nor the time."""

from benchmark import peaks_nemotron_h
from benchmark.layers._ssm_slice import peak, ssm_slice


def read(run, name):
    got = ssm_slice(run, "decode", "moe_expert_ffn")
    if got is None or not got[1].get("moe_experts_touched_total"):
        return None
    sec, c = got
    least = c["moe_experts_touched_total"] \
        * peaks_nemotron_h.expert_bytes(run["model"]) \
        / peak(run, "hbm_bytes_per_s")
    return 100.0 * least / sec
