"""Model step, the whole step of a model whose mixer keeps a recurrent
state: FLOPs a processed token from shapes
(``lobench/counts_retention.py``: the projections and the gate, 2 x 8 x
8,256 x 129 for the state's update and 2 x 40 x 8,256 x 129 for the
read-out a layer, the FFN, the head) times the processed tokens a
second the clients saw in the window (prompt and output alike: a prompt
token costs the state what an output token does), over the chip's bf16
peak.  Low by nature: the step is bound by the bytes of weights and
states, and the recurrence runs on the vector unit in float32; it is
the share of the whole step beside the kernel's."""

from lobench import counts_retention


def read(record, run):
    win = record.get("window")
    if not win or not win["processed_tokens"]:
        return None
    rate = win["processed_tokens"] / win["seconds"]
    return 100.0 * counts_retention.forward_flops_per_token(run.cp) \
        * rate / run.peaks["flops_bf16"]
