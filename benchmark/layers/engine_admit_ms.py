"""Mean milliseconds of the ``llm/admit`` phase (queue pop, page
reservation, prompt staging, the prefill dispatch) over the window's
passes that admitted at least one request (``benchmark/spans.py``)."""

from benchmark import spans


def read(run, name):
    return spans.read(run, spans.admit_ms)
