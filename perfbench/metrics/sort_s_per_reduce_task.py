"""Seconds a reduce task spent in the batch sorter (the ``agg.sort`` span's
self time: adding batches and producing the sorted output), per reduce
task."""


def read(w):
    n = sum(1 for t in w.tasks if t["kind"] == "reduce")
    if not n or not w.counter("trace_span_self_us_total"):
        return None
    return w.counter("trace_span_self_us_total", span="agg.sort") / 1e6 / n
