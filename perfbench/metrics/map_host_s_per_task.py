"""Host seconds of a map task's own work: the self times of the
``record.partition``, ``record.serialize`` and ``write.commit`` spans, per
map task."""

SPANS = ("record.partition", "record.serialize", "write.commit")


def read(w):
    n = sum(1 for t in w.tasks if t["kind"] == "map")
    if not n or not w.counter("trace_span_self_us_total"):
        return None
    return sum(w.counter("trace_span_self_us_total", span=s) for s in SPANS) / 1e6 / n
