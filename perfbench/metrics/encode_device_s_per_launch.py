"""Seconds an encode launch's drain waited on the device (queue, run and
D2H: the ``codec.encode.device`` span's self time), per device encode
launch. None where the program has no such span or counter."""


def read(w):
    launches = w.counter("codec_launches_total", op="encode")
    if not launches:
        return None
    return w.counter("trace_span_self_us_total", span="codec.encode.device") / 1e6 / launches
