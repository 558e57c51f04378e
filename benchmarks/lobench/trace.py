"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers: the
device's busy time as the union of its operations' intervals, time by
operation, the device time of each run of a compiled program, and the
longest idle gaps named by what the host was doing in them.

Timestamps in a trace count nanoseconds from the start of the profiling
session on every plane alike; :func:`capture` writes one host
annotation at a known wall-clock time so the program's own spans (which
carry epoch seconds) can be laid on the same clock."""

from __future__ import annotations

import contextlib
import re
import shutil
import time
from pathlib import Path

ANCHOR = "lobench:anchor"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


class Capture:
    """What :func:`capture` leaves behind."""

    def __init__(self, logdir: Path):
        self.logdir = logdir
        self.anchor_wall_s: float | None = None
        self.window_s: float | None = None

    def xplane(self) -> Path:
        found = sorted(self.logdir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no xplane file under {self.logdir}")
        return found[-1]


@contextlib.contextmanager
def capture(logdir: Path):
    """Trace what runs inside the block.  The python tracer is off: it
    slows the host it measures and swells the file."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    cap = Capture(logdir)
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    t0 = time.perf_counter()
    try:
        cap.anchor_wall_s = time.time()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass
        yield cap
    finally:
        cap.window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: Operations that only contain others: their time is their children's.
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%multiply_add_
    fusion.12 = f32[..] fusion(..), kind=kLoop``.  The key is the
    opcode, with the fusion's or custom call's own name behind it:
    ``fusion:multiply_add_fusion``, ``custom-call:tpu_custom_call``,
    ``copy``."""
    head, _, rest = text.partition(" = ")
    stem = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    found = _OPCODE.search(" " + rest) if rest else None
    if not found:
        return stem
    opcode = found.group(1)
    if opcode == "custom-call":
        target = _TARGET.search(rest)
        return f"custom-call:{target.group(1) if target else stem}"
    return opcode if stem == opcode else f"{opcode}:{stem}"


def host_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9]+", "_", name)


def _union(intervals) -> tuple[float, list]:
    """(total length, merged intervals) of ``(start, end)`` pairs."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def reduce(profile, *, window_s: float, anchor_wall_s: float | None = None,
           spans: list | None = None, top: int = 10) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData`` (or anything with
    its ``planes`` / ``lines`` / ``events`` shape).  Returns seconds:
    ``busy_s`` averaged over the device planes, ``ops`` and ``modules``
    summed over them, and the ``top`` idle gaps of the busiest-looking
    first device."""
    device_planes, host_events, anchor_ns = [], [], None
    for plane in profile.planes:
        if plane.name.startswith("/device:") and \
                "CUSTOM" not in plane.name.upper():
            device_planes.append(plane)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor_ns = ev.start_ns
                    elif ev.duration_ns > 0:
                        host_events.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             "host:" + host_name(ev.name))
                        )
    ops: dict[str, float] = {}
    modules: dict[str, list] = {}
    busy, gaps_of_first = [], None
    for plane in device_planes:
        intervals = []
        for line in plane.lines:
            if line.name == _OPS_LINE:
                for ev in line.events:
                    key = op_name(ev.name)
                    if key.split(":")[0] in CONTAINERS:
                        continue
                    intervals.append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
                    ops[key] = ops.get(key, 0.0) + ev.duration_ns / 1e9
            elif line.name == _MODULES_LINE:
                for ev in line.events:
                    modules.setdefault(
                        ev.name.split("(")[0], []
                    ).append(ev.duration_ns / 1e9)
        if not intervals:
            continue
        total, merged = _union(intervals)
        busy.append(total / 1e9)
        if gaps_of_first is None:
            if anchor_ns is not None:  # the window's two ends are idle too
                end_ns = anchor_ns + window_s * 1e9
                merged = [[anchor_ns, anchor_ns]] + [
                    m for m in merged if m[1] > anchor_ns and m[0] < end_ns
                ] + [[end_ns, end_ns]]
            gaps_of_first = [
                (b[0] - a[1], a[1], b[0])
                for a, b in zip(merged, merged[1:]) if b[0] > a[1]
            ]
    if not busy:
        return {"busy_s": 0.0, "window_s": window_s, "ops": {},
                "modules": {}, "device_ops": [], "idle_gaps": []}
    # Program spans on the trace's clock, innermost (shortest) first.
    covers = list(host_events)
    if anchor_ns is not None and anchor_wall_s is not None:
        for sp in spans or []:
            if sp.get("start") is None:
                continue
            start = anchor_ns + (sp["start"] - anchor_wall_s) * 1e9
            covers.append(
                (start, start + sp["durationS"] * 1e9,
                 "span:" + host_name(sp["name"]))
            )
    gaps = sorted(gaps_of_first or [], reverse=True)[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "ops": ops,
        "modules": modules,
        "device_ops": [
            [k, v] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [_name_gap(start, end, covers), length / 1e9]
            for length, start, end in gaps
        ],
    }


def _name_gap(start: float, end: float, covers: list) -> str:
    """The shortest span or host event that covers at least half of the
    gap; a program span wins over a host event of any length."""
    best = None
    for c_start, c_end, name in covers:
        overlap = min(end, c_end) - max(start, c_start)
        if overlap * 2 < end - start:
            continue
        rank = (0 if name.startswith("span:") else 1, c_end - c_start)
        if best is None or rank < best[0]:
            best = (rank, name)
    return best[1] if best else "host:unknown"


def read(cap: Capture, spans: list | None = None) -> dict:
    from jax.profiler import ProfileData

    return reduce(
        ProfileData.from_file(str(cap.xplane())),
        window_s=cap.window_s, anchor_wall_s=cap.anchor_wall_s,
        spans=spans,
    )
