"""Decode engine, generation by diffusion over blocks: tokens the
denoising forwards fixed over the window's denoise + commit slot-steps,
the engine's own counts (``stats()["tokensFixed"]``,
``["blockSteps"]``).  With T denoising steps a block of B takes T + 1
forwards: B / (T + 1)."""


def read(record, run):
    eng = (record.get("window") or {}).get("engine")
    if not eng or not eng.get("denoise", 0) + eng.get("commit", 0):
        return None
    return eng["fixed"] / (eng["denoise"] + eng["commit"])
