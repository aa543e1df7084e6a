"""Self time of the root task spans (``task.map``, ``task.reduce``: what no
layer's span covers) over the summed wall of every task, in percent."""


def read(w):
    wall = sum(t["t1"] - t["t0"] for t in w.tasks)
    if wall <= 0 or not w.counter("trace_span_self_us_total"):
        return None
    roots_us = (w.counter("trace_span_self_us_total", span="task.map")
                + w.counter("trace_span_self_us_total", span="task.reduce"))
    return 100.0 * roots_us / 1e6 / wall
