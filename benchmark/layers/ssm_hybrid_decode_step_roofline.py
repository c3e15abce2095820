"""The whole decode step's share of its roofline, for a model of
state-space, attention and latent-expert layers: the least bytes the
slice's decode steps must move (every layer's fixed weights and the
head once a step, every live row's state and window read and written,
the held experts that got a token, the cached tokens attended:
``peaks_nemotron_h.decode_steps_bytes``) over the published HBM rate,
over the decode program's device time. It reads the same whatever
implements the step."""

from benchmark import peaks_nemotron_h
from benchmark.layers._ssm_slice import peak, ssm_slice


def read(run, name):
    got = ssm_slice(run, "decode")
    if got is None or "kv_ctx_tokens_total" not in got[1]:
        return None
    sec, c = got
    m = run["model"]
    least = peaks_nemotron_h.decode_steps_bytes(
        m, c["ssm_layer_steps_total"] / len(m.layers_of("M")),
        c["ssm_rows_total"], c["moe_experts_touched_total"],
        c["kv_ctx_tokens_total"]) / peak(run, "hbm_bytes_per_s")
    return 100.0 * least / sec
