"""Routed experts (``ops/moe.py``): of a layer's experts, the share a
step's rows reached, the mean over the window's steps by the program's
own count (``stats()["expertsHit"]``: distinct experts a layer, summed
over layers and steps)."""


def read(record, run):
    eng = (record.get("window") or {}).get("engine")
    if not eng or not eng.get("steps") or "experts_hit" not in eng:
        return None
    per_layer_step = eng["experts_hit"] / (
        eng["steps"] * run.cp["num_layers"]
    )
    return 100.0 * per_layer_step / run.cp["num_experts"]
