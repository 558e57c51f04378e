"""Model step of a block-diffusion sparse-expert model: the bf16 bytes
one step must read (dense weights, the experts its rows reached by the
program's own count, the head, the K and V attended) at the chip's HBM
bandwidth, over the mean device time of a step program in the trace.
Experts and keys are means over the traced turns' ``lo:decode.step``
annotations (``experts_hit``, ``keys`` over ``positions`` times the
live slots' positions).  Memory-bound."""

from lobench import counts_moe, hostspans


def read(record, run):
    traced = record.get("trace")
    spans = hostspans.of(run)
    if not traced or spans is None:
        return None
    runs = [
        s for name, rs in traced["modules"].items()
        if name.startswith(hostspans.STEP_PROGRAM) for s in rs
    ]
    turns = [st for *_e, st in spans.named("decode.step")
             if int(st.get("positions", 0))]
    if not runs or not turns:
        return None
    width = run.cp["block_length"]
    hit = sum(int(st["experts_hit"]) for st in turns) / len(turns)
    # ``keys`` sums, over a turn's positions, the keys each attended:
    # every position of a slot's block attends the same keys
    keys = sum(int(st["keys"]) for st in turns) / len(turns) / width
    least = counts_moe.step_bytes(run.cp, hit, keys) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(runs) / len(runs))
