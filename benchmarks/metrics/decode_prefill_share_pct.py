"""Decode engine: the share of the traced steps' slot-steps that
consumed a prompt token (prefill, one token a step) and produced no
output, as the engine counts them itself: the ``prompt`` and ``output``
metadata of its ``lo:decode.step`` annotations
(``serve/decode/engine.py`` ``_ModelDecoder._run``)."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    steps = spans.named("decode.step") if spans is not None else []
    prompt = sum(int(st.get("prompt", 0)) for *_e, st in steps)
    output = sum(int(st.get("output", 0)) for *_e, st in steps)
    if prompt + output == 0:
        return None
    return 100.0 * prompt / (prompt + output)
