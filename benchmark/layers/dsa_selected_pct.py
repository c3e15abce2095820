"""The share of the scored positions that sparse attention read, over
the window: 100 x delta of the engine's ``sparse_selected_tokens_total``
over delta of ``index_ctx_tokens_total`` (min(len + 1, index_topk) over
len + 1, summed over rows, layers and steps). 100 while every row is
shorter than ``index_topk``; it says how much of the cache the
selection left unread."""


def read(run, name):
    c = run["counters"]
    scored = c.get("index_ctx_tokens_total")
    if not scored or "sparse_selected_tokens_total" not in c:
        return None
    return 100.0 * c["sparse_selected_tokens_total"] / scored
