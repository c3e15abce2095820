"""Prompt tokens the engine prefilled during the traced slice (its
``prefill_tokens_total`` counter, read at the slice's two ends on the
host, so off by up to one prefill at each end) over the device time
inside its prefill programs in that slice."""


def read(run, name):
    t = run.get("trace")
    if not t or not t["devices"]:
        return None
    mods = t["devices"][0]["modules"]
    sec = sum(mods[p][1] for kind, progs in run["programs"].items()
              if kind.startswith("prefill") for p in progs if p in mods)
    tokens = t["slice_counters"]["prefill_tokens"]
    return tokens / sec if sec and tokens else None
