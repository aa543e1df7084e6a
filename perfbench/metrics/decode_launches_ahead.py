"""Launches already dispatched and not yet drained on the same device when
a decode launch was dispatched, averaged over decode launches
(``codec_launches_ahead_total{op=decode}``)."""


def read(w):
    launches = w.counter("codec_launches_total", op="decode")
    if not launches:
        return None
    return w.counter("codec_launches_ahead_total", op="decode") / launches
