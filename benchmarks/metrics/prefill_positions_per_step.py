"""Decode engine: prompt tokens a prefill slot-step moves past, as the
engine counts them itself: Σ ``prompt_positions`` over Σ ``prompt`` of
the traced ``lo:decode.step`` annotations that carry the key
(``serve/decode/engine.py`` ``_dispatch``: a live slot with prompt
beyond its position is a ``prompt`` slot-step and moves past up to
``pages.PROMPT_CHUNK`` prompt tokens in the one step, a prompt's
``t0 - 1`` in all: what one-token prefill takes a slot-step each for;
cumulative in ``stats()["promptPositions"]`` beside
``["slotSteps"]["prompt"]``).  1 where a prompt is fed one token a
step; near the chunk's width where prompts are long beside it, less by
each prompt's last, partly filled chunk.  A program whose annotations
carry no such key reads nothing."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    steps = spans.named("decode.step") if spans is not None else []
    counted = [st for *_e, st in steps if "prompt_positions" in st]
    prompt = sum(int(st.get("prompt", 0)) for st in counted)
    if not prompt:
        return None
    return sum(int(st["prompt_positions"]) for st in counted) / prompt
