"""Seconds a map task blocked on the encode window (the
``codec.encode.harvest`` span's self time), per map task."""


def read(w):
    n = sum(1 for t in w.tasks if t["kind"] == "map")
    if not n or not w.counter("trace_span_self_us_total"):
        return None
    return w.counter("trace_span_self_us_total", span="codec.encode.harvest") / 1e6 / n
