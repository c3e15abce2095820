"""Device time inside executions of the engine's decode program per
execution, from the ``XLA Modules`` line of device 0."""


def read(run, name):
    t = run.get("trace")
    if not t or not t["devices"]:
        return None
    mods = t["devices"][0]["modules"]
    n = sec = 0.0
    for prog in run["programs"].get("decode", []):
        if prog in mods:
            n += mods[prog][0]
            sec += mods[prog][1]
    return sec / n * 1e3 if n else None
