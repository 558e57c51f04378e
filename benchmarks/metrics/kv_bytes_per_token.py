"""Decode engine (``serve/decode/pages.py`` ``PagePool``): resident
cache bytes a cached position, all layers: the pool's ``page_bytes()``
over slots x pages, as the engine says it in the ``kv_bytes_per_token``
metadata of its ``lo:decode.step`` annotations (``stats()``:
``kvBytesPerToken`` of each pool).  8,064 for seven layers of one
576-wide bfloat16 latent row; 286,720 if per-head keys and values were
cached instead."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    steps = spans.named("decode.step") if spans is not None else []
    said = [float(st["kv_bytes_per_token"]) for *_e, st in steps
            if float(st.get("kv_bytes_per_token", 0)) > 0]
    return max(said) if said else None
