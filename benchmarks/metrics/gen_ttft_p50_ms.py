"""Decode engine: median over every request sent inside the window of
sent -> first SSE token, timed from outside.  Which requests a window
holds moves it, so it stands here and decides no PR (PERF.md)."""

import statistics


def read(record, run):
    win = record.get("window")
    if not win or not win["ttfts"]:
        return None
    return statistics.median(win["ttfts"])
