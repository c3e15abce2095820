"""What the three roofline readers of the ``deepseek_v3`` cells share:
the decode programs' device seconds, or those of their ops whose name
holds a given string, and the family's step counters over the traced slice (counted when a step drains, so off
by up to the two steps in flight at each end of the slice: one part in a
hundred at 200 steps)."""


def decode_slice(run, op_substring=None):
    """``(seconds, slice_counters)`` or None where the run has no
    trace, no decode program in it, no such op or no such counters."""
    t = run.get("trace")
    if not t or not t["devices"] or run.get("model") is None:
        return None
    c = t.get("slice_counters") or {}
    if not c.get("moe_layer_steps_total"):
        return None
    d0 = t["devices"][0]
    progs = run["programs"].get("decode", [])
    if op_substring is None:
        sec = sum(d0["modules"][p][1] for p in progs if p in d0["modules"])
    else:
        sec = sum(v for k, v in d0["ops"].items()
                  if k.split(":", 1)[0] in progs and op_substring in k)
    return (sec, c) if sec else None


def hbm_rate(run) -> float:
    from benchmark.peaks import peaks
    return peaks(run["device"]["kind"])["hbm_bytes_per_s"]
