"""Device milliseconds of the selection a decode step: the time of the
decode programs' selection sorts (``_sparse_slice.is_selection``) over
the slice, over the decode programs' executions in it."""

from benchmark.layers._sparse_slice import dsa_slice, is_selection


def read(run, name):
    if dsa_slice(run) is None:
        return None
    d0 = run["trace"]["devices"][0]
    progs = run["programs"].get("decode", [])
    n = sum(d0["modules"][p][0] for p in progs if p in d0["modules"])
    topk = run["model"].index_topk
    sec = sum(v for k, v in d0["ops"].items()
              if k.split(":", 1)[0] in progs and is_selection(k, topk))
    return sec / n * 1e3 if n and sec else None
