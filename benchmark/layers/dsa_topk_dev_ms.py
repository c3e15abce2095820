"""Device milliseconds of the decode step's selection kernel a decode
step: the decode programs' device seconds in the ops whose name holds
``dsa_topk_decode`` (``kernels/sparse_attention``'s exact top-k without
a sort), over the decode programs' executions in the slice. A program
that selects by a sort has no such op and reads nothing."""

from benchmark.layers._sparse_slice import dsa_slice


def read(run, name):
    got = dsa_slice(run, "dsa_topk_decode")
    if got is None:
        return None
    d0 = run["trace"]["devices"][0]
    n = sum(d0["modules"][p][0] for p in run["programs"].get("decode", [])
            if p in d0["modules"])
    return got[0] / n * 1e3 if n else None
