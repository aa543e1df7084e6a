"""Pointer-jump rounds the TLZ decode program ran per device decode launch
(``codec_decode_jump_rounds_total`` over ``codec_launches_total{op=decode}``).
None where no decode launched, or where the program has no such counter."""


def read(w):
    launches = w.counter("codec_launches_total", op="decode")
    rounds = w.counter("codec_decode_jump_rounds_total")
    if not launches or not rounds:
        return None
    return rounds / launches
