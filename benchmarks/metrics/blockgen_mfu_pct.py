"""Model step, the whole step of a block-diffusion model: FLOPs a
processed position (active parameters, attention over the keys
attended, the head) from shapes, at the mean number of keys the
window's positions attended, times positions a second, over the chip's
bf16 peak.  Positions and keys are the engine's own counts over the
window (``stats()["positions"]``, ``["keysAttended"]``), not the
client's clock."""

from lobench import counts_moe


def read(record, run):
    eng = (record.get("window") or {}).get("engine")
    if not eng or not eng.get("positions"):
        return None
    flops = counts_moe.forward_flops_per_position(
        run.cp, eng["keys"] / eng["positions"]
    )
    rate = eng["positions"] / record["window"]["seconds"]
    return 100.0 * flops * rate / run.peaks["flops_bf16"]
