"""Share of the traced slice in which no operation ran on the device:
1 - union of device-op intervals / slice, mean over the chips used."""


def read(run, name):
    t = run.get("trace")
    if not t or not t["devices"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
