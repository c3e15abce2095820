"""Slots seated in the state class a decoding row, over the window: the
engine's ``state_slots_held_total`` (at every decode dispatch, the slots
its state ledger has seated) over ``decode_rows_total``. The ledger's
witness: 1 whatever the contexts (a little above it while a request's
last step is in flight: it is seated and no longer dispatched); a slot
that was never released reads above that for good."""


def read(run, name):
    c = run["counters"]
    if not c.get("decode_rows_total") \
            or "state_slots_held_total" not in c:
        return None
    return c["state_slots_held_total"] / c["decode_rows_total"]
