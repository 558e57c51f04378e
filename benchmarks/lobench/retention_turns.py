"""What the traced turns of a model with a recurrent state say of
themselves: the ``lo:decode.step`` annotations' counts, summed
(``serve/decode/engine.py``: ``prompt`` and ``output``, the live slots
a turn's step stepped; ``pools``, the pools it stepped;
``state_bytes_per_slot`` of the pool's allocated leaves; the
``state_resets`` beside them have no reader).  A program whose annotations carry no
``state_bytes_per_slot`` (the parent of the PR that added it; a model
with pages) gives None."""

from __future__ import annotations

from lobench import hostspans


def read(run) -> dict | None:
    spans = hostspans.of(run)
    if spans is None:
        return None
    stepped = [st for *_e, st in spans.named("decode.step")
               if int(st.get("slots", 0))]
    if not stepped \
            or not float(stepped[0].get("state_bytes_per_slot", 0)):
        return None

    def total(key):
        return sum(int(st.get(key, 0)) for st in stepped)

    return {
        "spans": spans,
        "dispatched": len(stepped),
        "slot_steps": total("prompt") + total("output"),
        "pools": total("pools"),
        "state_bytes_per_slot": max(
            float(st["state_bytes_per_slot"]) for st in stepped
        ),
    }
