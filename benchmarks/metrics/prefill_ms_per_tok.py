"""Decode engine, prefill: median over the window's requests of time to
first token over prompt length, timed from outside."""

import statistics


def read(record, run):
    win = record.get("window")
    if not win or not win["prefill_ms_per_tok"]:
        return None
    return statistics.median(win["prefill_ms_per_tok"])
