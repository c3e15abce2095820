"""Routed experts that received at least one token, per expert layer and
decode step, over the window: delta of the engine's
``moe_experts_touched_total`` (counted on the device, fetched with the
step's tokens) over delta of ``moe_layer_steps_total``. It says how full
the batch was: a step reads that many experts' weights."""


def read(run, name):
    c = run["counters"]
    steps = c.get("moe_layer_steps_total")
    if not steps or "moe_experts_touched_total" not in c:
        return None
    return c["moe_experts_touched_total"] / steps
