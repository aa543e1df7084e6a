"""Seconds a decode launch's drain waited on the device (queue, run and
D2H: the ``codec.decode.device`` span's self time), per device decode
launch. None where the program has no such span or counter."""


def read(w):
    launches = w.counter("codec_launches_total", op="decode")
    if not launches:
        return None
    return w.counter("trace_span_self_us_total", span="codec.decode.device") / 1e6 / launches
