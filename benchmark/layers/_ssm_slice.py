"""What the four roofline readers of the ``nemotron_h`` cells share: the
device seconds of the engine's decode or prefill programs in the traced
slice, or of their ops whose name holds a given string, and the
family's step counters over that slice (counted when a step drains or a
prefill is dispatched, so off by up to the two steps in flight at each
end of the slice). None where the run has no trace, no such program or
op in it, or a program without the family's counters (the parent of the
PR that added them)."""

from benchmark.layers._retention_slice import peak  # noqa: F401


def ssm_slice(run, kind: str, op_substring=None):
    """``(seconds, slice_counters)`` of the programs whose kind starts
    with ``kind`` (``"decode"`` | ``"prefill"``), or None."""
    t = run.get("trace")
    if not t or not t["devices"] or run.get("model") is None:
        return None
    c = t.get("slice_counters") or {}
    if not c.get("ssm_layer_steps_total"):
        return None
    d0 = t["devices"][0]
    progs = [p for k, ps in run["programs"].items()
             if k.startswith(kind) for p in ps]
    if op_substring is None:
        sec = sum(d0["modules"][p][1] for p in progs if p in d0["modules"])
    else:
        sec = sum(v for k, v in d0["ops"].items()
                  if k.split(":", 1)[0] in progs and op_substring in k)
    return (sec, c) if sec else None
