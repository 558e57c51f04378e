"""Decode engine: window seconds over the growth of
``DecodeEngine.stats()["steps"]``."""


def read(record, run):
    win = record.get("window")
    if not win or not win["engine_steps"]:
        return None
    return 1e3 * win["seconds"] / win["engine_steps"]
