"""The expert product's share of its roofline inside the decode program.
The least time is the bytes the slice's expert layers must read (every
routed expert that got a token, by the engine's
``moe_experts_touched_total``, and per layer and step the shared expert
and the router: ``peaks_deepseek.moe_ffn_bytes``) over the published HBM
rate; the share is that over the device time of the ``moe_expert_ffn``
kernel, which computes the routed and the shared experts. The router's
product runs in an XLA fusion the trace cannot name: its 0.5 MB a layer
is in the bytes and not in the time, 0.05 % high."""

from benchmark import peaks_deepseek
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run, "moe_expert_ffn")
    if got is None:
        return None
    sec, c = got
    least = peaks_deepseek.moe_ffn_bytes(
        run["model"], c["moe_experts_touched_total"],
        c["moe_layer_steps_total"]) / hbm_rate(run)
    return 100.0 * least / sec
