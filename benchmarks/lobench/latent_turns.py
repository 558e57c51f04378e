"""What the traced turns of a latent-attention, shared-experts decoder
say of themselves: the ``lo:decode.step`` annotations' counts, summed
(``serve/decode/engine.py``: ``keys``, ``prompt``, ``output``,
``slots`` of the step a turn dispatched; ``experts_hit``,
``expert_rows``, ``load_max`` of the step it read, which is the one
dispatched the turn before).  A program whose annotations carry no
``expert_rows`` (the parent of the PR that added them; a dense model)
gives None."""

from __future__ import annotations

from lobench import hostspans


def read(run) -> dict | None:
    spans = hostspans.of(run)
    if spans is None:
        return None
    steps = [st for *_e, st in spans.named("decode.step")]
    stepped = [st for st in steps if int(st.get("slots", 0))]
    counted = [st for st in steps if int(st.get("load_max", 0))]
    if not stepped or not counted or "expert_rows" not in counted[0]:
        return None

    def total(turns, key):
        return sum(int(st.get(key, 0)) for st in turns)

    return {
        "spans": spans,
        "dispatched": len(stepped),
        "read": len(counted),
        "keys": total(stepped, "keys"),
        "tokens": total(stepped, "prompt") + total(stepped, "output"),
        "experts_hit": total(counted, "experts_hit"),
        "expert_rows": total(counted, "expert_rows"),
        "kv_bytes_per_token": max(
            float(st.get("kv_bytes_per_token", 0)) for st in stepped
        ),
    }
