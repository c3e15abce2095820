"""Host milliseconds an admission costs before the device has work
again: from the start of an ``llm/admit`` sweep that prefilled somebody
to the end of its first ``llm/prefill_dispatch`` (the sweep proper, the
staging and the jit call; the step in flight was drained ahead of it),
averaged over the sweeps that start in the window
(``benchmark/spans_admission.py``)."""

from benchmark import spans, spans_admission


def read(run, name):
    return spans.read(run, spans_admission.stage_ms)
