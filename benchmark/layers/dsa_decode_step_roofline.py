"""The whole sparse decode step's share of its roofline: the least bytes
the slice's decode steps must move (every layer's attention and
indexer, the dense FFN, routers and shared experts, the head, the held
experts that got a token, the cached index keys scored and the latent
rows selected: ``peaks_glm5.decode_steps_bytes``) over the published
HBM rate, over the decode program's device time. It reads the same
whatever implements the step."""

from benchmark import peaks_glm5
from benchmark.layers._decode_slice import hbm_rate
from benchmark.layers._sparse_slice import dsa_slice


def read(run, name):
    got = dsa_slice(run)
    if got is None:
        return None
    sec, c = got
    m = run["model"]
    least = peaks_glm5.decode_steps_bytes(
        m, c["moe_layer_steps_total"] / m.num_moe_layers,
        c["moe_experts_touched_total"], c["index_key_bytes_total"],
        c["sparse_latent_bytes_total"]) / hbm_rate(run)
    return 100.0 * least / sec
