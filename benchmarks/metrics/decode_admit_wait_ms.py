"""Decode engine: mean wait of a stream for its slot, arrival to
seated, over the streams seated inside the traced window: the
``wait_ms`` of the engine's ``lo:decode.seat`` annotations
(``serve/decode/engine.py`` ``_ModelDecoder._admit``)."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    waits = [
        float(st["wait_ms"])
        for *_e, st in (spans.named("decode.seat") if spans else [])
        if "wait_ms" in st
    ]
    return sum(waits) / len(waits) if waits else None
