"""Host seconds of the device codec per launch: the self times of the
``codec.encode.stage``, ``codec.encode.assemble``, ``codec.decode.parse``
and ``codec.decode.emit`` spans over every encode and decode launch."""

SPANS = ("codec.encode.stage", "codec.encode.assemble", "codec.decode.parse",
         "codec.decode.emit")


def read(w):
    launches = w.counter("codec_launches_total")
    if not launches:
        return None
    host_us = sum(w.counter("trace_span_self_us_total", span=s) for s in SPANS)
    return host_us / 1e6 / launches
