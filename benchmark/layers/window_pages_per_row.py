"""Pages of the window class a decoding row holds, over the window: the
engine's ``window_pages_held_total`` (at every decode dispatch, the
pages its rows own in the window class's ledger) over
``decode_rows_total``. The allocator's witness: it stays at the ring's
pages however long the contexts are; a window layer that owned a
full-length chain would read length / page."""


def read(run, name):
    c = run["counters"]
    if not c.get("decode_rows_total") \
            or "window_pages_held_total" not in c:
        return None
    return c["window_pages_held_total"] / c["decode_rows_total"]
