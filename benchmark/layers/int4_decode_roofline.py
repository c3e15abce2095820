"""The INT4 matmul kernel's share of its roofline inside the decode
program. Decode at <= 16 rows is bound by HBM: the least time a step can
take is the packed weights and scales it must stream once (computed from
the configuration's sizes by ``peaks.q4_weight_bytes``) over the
published HBM rate; the share is that over the kernel's summed device
time per step. Ops of a step that straddles an end of the slice are
partly left out, so the share can read up to one step in fifty high."""

from benchmark.peaks import peaks, q4_weight_bytes


def read(run, name):
    t, cfg = run.get("trace"), run.get("model")
    if not t or not t["devices"] or cfg is None:
        return None
    d0 = t["devices"][0]
    progs = run["programs"].get("decode", [])
    steps = sum(d0["modules"][p][0] for p in progs if p in d0["modules"])
    sec = sum(v for k, v in d0["ops"].items()
              if k.split(":", 1)[0] in progs and "int4_matmul" in k)
    if not steps or not sec:
        return None
    h, f = cfg.hidden_size, cfg.intermediate_size
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    layer = (q4_weight_bytes(h, q + 2 * kv) + q4_weight_bytes(q, h)
             + q4_weight_bytes(h, 2 * f) + q4_weight_bytes(f, h))
    step_bytes = cfg.num_hidden_layers * layer \
        + q4_weight_bytes(h, cfg.vocab_size)
    least = step_bytes / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (sec / steps)
