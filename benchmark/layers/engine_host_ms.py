"""Host seconds the engine thread spent per pass in the window:
delta of ``LLMServer.host_seconds`` over delta of ``LLMServer.steps``."""


def read(run, name):
    c = run["counters"]
    if not c.get("passes"):
        return None
    return c["host_seconds"] / c["passes"] * 1e3
