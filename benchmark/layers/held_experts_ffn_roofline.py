"""The held experts' product's share of its roofline inside the decode
program. The least time is the bytes the slice's expert layers must
read (every held expert that got a token, by the engine's
``moe_experts_touched_total``, and per layer and step the router over
all the experts: ``peaks_mimo.held_experts_bytes``) over the published
HBM rate; the share is that over the device time of the
``moe_expert_ffn`` kernel. The router's product runs in an XLA fusion
the trace cannot name: its 2.1 MB a layer is in the bytes and not in
the time, which reads the share some 0.7 % high at 6 experts touched."""

from benchmark import peaks_mimo
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run, "moe_expert_ffn")
    if got is None or not hasattr(run["model"], "experts_held"):
        return None
    sec, c = got
    least = peaks_mimo.held_experts_bytes(
        run["model"], c["moe_experts_touched_total"],
        c["moe_layer_steps_total"]) / hbm_rate(run)
    return 100.0 * least / sec
