"""Seconds a reduce task blocked on the decode window (the
``codec.decode.harvest`` span's self time), per reduce task."""


def read(w):
    n = sum(1 for t in w.tasks if t["kind"] == "reduce")
    if not n or not w.counter("trace_span_self_us_total"):
        return None
    return w.counter("trace_span_self_us_total", span="codec.decode.harvest") / 1e6 / n
