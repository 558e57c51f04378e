"""Model step of a latent-attention model with shared and held routed
experts: the bf16 bytes one step must read (the non-expert weights, the
held experts its rows reached by the program's own count, the head,
the latent rows attended, each once) at the chip's HBM bandwidth, over
the mean device time of a step program in the trace.  Experts reached
and keys are means over the traced turns' ``lo:decode.step``
annotations.  Memory-bound."""

from lobench import counts_mla, hostspans, latent_turns


def read(record, run):
    traced, turns = record.get("trace"), latent_turns.read(run)
    if not traced or not turns:
        return None
    runs = [
        s for name, rs in traced["modules"].items()
        if name.startswith(hostspans.STEP_PROGRAM) for s in rs
    ]
    if not runs:
        return None
    least = counts_mla.step_bytes(
        run.cp, turns["experts_hit"] / turns["read"],
        turns["keys"] / turns["dispatched"],
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(runs) / len(runs))
