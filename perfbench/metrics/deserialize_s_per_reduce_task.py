"""Seconds a reduce task spent turning decoded bytes into batches (the
``record.deserialize`` span's self time), per reduce task."""


def read(w):
    n = sum(1 for t in w.tasks if t["kind"] == "reduce")
    if not n or not w.counter("trace_span_self_us_total"):
        return None
    return w.counter("trace_span_self_us_total", span="record.deserialize") / 1e6 / n
