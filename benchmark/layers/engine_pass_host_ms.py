"""Host milliseconds of the engine thread per pass in the window, from
the engine's own spans: the duration of each ``llm/pass`` less its
``llm/fence_wait`` phases, averaged over the passes that start in the
window (``benchmark/spans.py``). Unlike ``engine_host_ms`` it covers
admission and the drain's bookkeeping."""

from benchmark import spans


def read(run, name):
    return spans.read(run, spans.pass_host_ms)
