"""Milliseconds of the window spent in garbage collections of 0.5 ms or
more (``py/gc`` records, any thread: a collection stops them all); 0.0
when the ring is whole and holds none. Nothing where the program does
not watch its collector (``bigdl_tpu.observability.tracing.watch_gc``
counts every collection it sees, so a watcher that ran has counted).
Says how full the ring is on the way."""

from benchmark import spans, spans_admission


def read(run, name):
    from bigdl_tpu.observability import tracing
    spans_admission.say_fill(run)
    if not sum(getattr(tracing, "gc_collections_total", ())):
        return None
    return spans.read(run, spans_admission.gc_ms)
