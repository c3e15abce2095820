"""Mean milliseconds of ``llm/prefill_finish``, a prefill's epilogue on
the engine thread: its eager table updates queue behind the prefill just
dispatched, so where the host waits in one of them the time is here and
the device is busy (``benchmark/spans_admission.py``)."""

from benchmark import spans, spans_admission


def read(run, name):
    return spans.read(run, spans_admission.prefill_finish_ms)
