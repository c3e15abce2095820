"""95th percentile (nearest rank) of the token gaps as the engine thread
saw them: gaps between consecutive ``llm/drain`` ends that name the same
request, for the gaps that close in the window (``benchmark/spans.py``).
What it differs by from ``itl_p95_ms`` is the poller's error."""

from benchmark import spans, stats


def _p95(records, t_open, t_close):
    gaps = spans.itl_ms(records, t_open, t_close)
    return stats.percentile(gaps, 95) if gaps else None


def read(run, name):
    return spans.read(run, _p95)
