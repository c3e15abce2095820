"""The whole decode step's share of its roofline: the least bytes the
slice's decode steps must read (attention linears, the dense layer's
FFN, shared experts, routers, the head, the routed experts that got a
token, the cached latent rows: ``peaks_deepseek.decode_steps_bytes``)
over the published HBM rate, over the decode program's device time. It
reads the same whatever implements the step."""

from benchmark import peaks_deepseek
from benchmark.layers._decode_slice import decode_slice, hbm_rate


def read(run, name):
    got = decode_slice(run)
    if got is None:
        return None
    sec, c = got
    m = run["model"]
    n_moe = m.num_hidden_layers - m.first_k_dense_replace
    least = peaks_deepseek.decode_steps_bytes(
        m, c["moe_layer_steps_total"] / n_moe,
        c["moe_experts_touched_total"],
        c["latent_ctx_tokens_total"]) / hbm_rate(run)
    return 100.0 * least / sec
