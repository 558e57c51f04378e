"""Decode engine (``pages.py``): live slots, sampled from
``DecodeEngine.stats()`` through the window."""


def read(record, run):
    win = record.get("window")
    if not win or not win["live_samples"]:
        return None
    return sum(win["live_samples"]) / len(win["live_samples"])
