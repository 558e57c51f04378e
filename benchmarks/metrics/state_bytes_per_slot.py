"""Decode engine (``serve/decode/pages.py`` ``PagePool``): resident
state bytes a slot, all layers, of a pool whose cache has no length
axis: the pool's ``page_bytes()`` over its slots, from the allocated
leaves, as the engine says it in the ``state_bytes_per_slot`` metadata
of its ``lo:decode.step`` annotations (``stats()``:
``stateBytesPerSlot`` of each pool).  7 layers x 8 heads x rows x (128
+ 1) x 4 B: 238,565,376 at the 8,256 products of a 128-wide key,
240,414,720 at the 8,320 rows the program pads them to (whole rows of
128 lanes), 473,432,064 if both u_a u_b and u_b u_a were kept.  A pool
of pages says 0 and the reader leaves the metric out."""

from lobench import retention_turns


def read(record, run):
    turns = retention_turns.read(run)
    return turns["state_bytes_per_slot"] if turns else None
