"""The whole decode step's share of its roofline, for a model of window
and full layers with an expert share: the least bytes the slice's decode
steps must read (every layer's attention linears, the dense layer's FFN,
the head, the routers, the held experts that got a token, both classes'
cached rows: ``peaks_mimo.decode_steps_bytes``) over the published HBM
rate, over the decode program's device time. It reads the same whatever
implements the step."""

from benchmark import peaks_mimo
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run)
    if got is None or "full_ctx_tokens_total" not in got[1]:
        return None
    sec, c = got
    m = run["model"]
    least = peaks_mimo.decode_steps_bytes(
        m, c["moe_layer_steps_total"] / m.num_moe_layers,
        c["moe_experts_touched_total"], c["full_ctx_tokens_total"],
        c["window_ctx_tokens_total"]) / hbm_rate(run)
    return 100.0 * least / sec
