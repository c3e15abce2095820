"""Share of the window's token gaps, in per cent, that closed with a
prefill dispatched since the row's previous token, as the engine counts
them where it applies a token (``gaps`` and ``gaps_behind_prefill`` on
``llm/drain``; ``benchmark/spans_admission.py``). Where admissions are
the only disturbance it is arrivals/s × live rows ÷ gaps/s."""

from benchmark import spans, spans_admission


def read(run, name):
    return spans.read(run, spans_admission.gaps_behind_prefill_pct)
