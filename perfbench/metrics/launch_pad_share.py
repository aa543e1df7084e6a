"""Share of launched rows that carry no block for the device (bucket
padding, or a decode row left to the host decoder), encode and decode
together, in percent (``codec_launch_rows_total{row}``). A count."""


def read(w):
    pad = w.counter("codec_launch_rows_total", row="pad")
    total = pad + w.counter("codec_launch_rows_total", row="real")
    if not total:
        return None
    return 100.0 * pad / total
