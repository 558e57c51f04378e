"""Decode engine (``serve/decode/engine.py`` ``_admit``): pools of the
model a turn stepped, the mean of the ``pools`` metadata over the
traced turns' ``lo:decode.step`` annotations (``stats()``:
``poolsLive``).  A model whose cache has no length axis serves every
length from ONE pool, so 1 whatever the mix; an attention model under
the same mix steps a pool a KV bucket, each step reading all the
weights.  A program whose annotations carry no ``pools`` (or a pool
of pages: ``lobench/retention_turns.py``) reads nothing."""

from lobench import retention_turns


def read(record, run):
    turns = retention_turns.read(run)
    return turns["pools"] / turns["dispatched"] if turns else None
