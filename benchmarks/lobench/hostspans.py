"""What the program itself wrote into a traced run's profile, beside
what the device did: the ``lo:`` host annotations of
``learningorchestra_tpu/obs/tracing.py`` (every ``span()`` of a job,
the decode worker's phases, its ``lo:decode.step`` with the step's
slot-step counts), the runs of the decode step program on the first
device, and the device seconds of each named Pallas kernel.

The profile is the ``.xplane.pb`` that :func:`lobench.trace.capture`
left under ``run.scratch / "trace"``: it is still there when the metric
readers run.  It is parsed once a run and kept on ``run``.  Every plane
counts nanoseconds from the start of the profiling session, so a host
annotation and a device event compare as they stand.

A program that writes no ``lo:`` annotation and names no kernel (the
parent of the PR that added this) gives empty lists: each reader then
has nothing to read and returns ``None``."""

from __future__ import annotations

import re
import time
from pathlib import Path

from lobench import trace

PREFIX = "lo:"
STEP_PROGRAM = "jit_step"
#: The decode worker's phases that stand between two device steps.
GAP_PHASES = ("sync", "emit", "admit", "dispatch")
#: bf16 tensors of batch x seq x hidden that each flash kernel must
#: read or write at the least: forward q, k, v -> o; dq reads q, k, v,
#: do and writes dq; dkv reads the same and writes dk, dv (the f32 row
#: statistics are small beside them and left out).
FLASH_TENSORS = {"flash_fwd": 4, "flash_dq": 5, "flash_dkv": 6}


class HostSpans:
    """``events``: ``(name, start_ns, end_ns, stats)`` of every ``lo:``
    host event, name without the prefix, by start.  ``steps``:
    ``(start_ns, end_ns)`` of every run of the decode step program on
    the first device, by start.  ``kernels``: device seconds by the
    name of the HLO instruction, of every custom call on the first
    device; ``calls``: how many events those seconds are of."""

    def __init__(self, events: list, steps: list, kernels: dict,
                 calls: dict):
        self.events = sorted(events, key=lambda e: e[1])
        self.steps = sorted(steps)
        self.kernels = kernels
        self.calls = calls

    def named(self, name: str) -> list:
        return [e for e in self.events if e[0] == name]


def instruction_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%flash_fwd.3 =
    (bf16[..]) custom-call(..)``, or by the instruction's name alone:
    the name without ``%`` and the trailing number XLA adds."""
    head = text.partition(" = ")[0].strip()
    return re.sub(r"[.\d]+$", "", head.lstrip("%")) or head


def reduce(profile) -> HostSpans:
    """``profile`` is a ``jax.profiler.ProfileData`` (or anything with
    its ``planes`` / ``lines`` / ``events`` shape)."""
    events, steps, kernels, calls = [], [], {}, {}
    first_device = None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        events.append((
                            ev.name[len(PREFIX):].split("#")[0],
                            ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(getattr(ev, "stats", None) or ()),
                        ))
        elif first_device is None and plane.name.startswith("/device:") \
                and "CUSTOM" not in plane.name.upper():
            first_device = plane
    for line in (first_device.lines if first_device is not None else ()):
        if line.name == trace._MODULES_LINE:
            steps += [
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events
                if ev.name.split("(")[0].startswith(STEP_PROGRAM)
            ]
        elif line.name == trace._OPS_LINE:
            for ev in line.events:
                if trace.op_name(ev.name).startswith("custom-call"):
                    name = instruction_name(ev.name)
                    kernels[name] = kernels.get(name, 0.0) \
                        + ev.duration_ns / 1e9
                    calls[name] = calls.get(name, 0) + 1
    return HostSpans(events, steps, kernels, calls)


def of(run) -> HostSpans | None:
    """The traced run's host spans, or None where there is no profile
    to read (an untraced run, a canned record in a test)."""
    cached = getattr(run, "_hostspans", None)
    if cached is not None:
        return cached
    scratch = getattr(run, "scratch", None)
    found = sorted(
        Path(scratch).glob("trace/plugins/profile/*/*.xplane.pb")
    ) if scratch is not None else []
    if not found:
        return None
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    spans = reduce(ProfileData.from_file(str(found[-1])))
    run._hostspans = spans
    note = getattr(run, "note", None)
    if note is not None:  # what the second parse costs a traced run
        note(hostspans_parse_s=round(time.perf_counter() - t0, 3),
             lo_events=len(spans.events), step_runs=len(spans.steps),
             kernels={k: [spans.calls[k], round(v, 6)]
                      for k, v in spans.kernels.items()})
    return spans


# -- the decode loop: what the host did while the chip waited ---------------


def gap_split(spans: HostSpans | None) -> dict | None:
    """Mean milliseconds, over the traced steps, of the gap before a
    run of the step program (from the end of the run before it) and of
    that gap's overlap with each of the worker's phases; ``unnamed`` is
    the rest.  None without two step runs and one phase annotation."""
    if spans is None or len(spans.steps) < 2:
        return None
    phases = {
        name: [(s, e) for _n, s, e, _st in spans.named(f"decode.{name}")]
        for name in GAP_PHASES
    }
    if not any(phases.values()):
        return None
    gaps = [
        (prev_end, start)
        for (_s, prev_end), (start, _e) in zip(spans.steps, spans.steps[1:])
        if start > prev_end
    ]
    if not gaps:
        return None
    total = {name: 0.0 for name in GAP_PHASES}
    for name, intervals in phases.items():
        i = 0
        for g0, g1 in gaps:  # both by start: one pass
            while i < len(intervals) and intervals[i][1] <= g0:
                i += 1
            j = i
            while j < len(intervals) and intervals[j][0] < g1:
                total[name] += max(
                    0.0, min(g1, intervals[j][1]) - max(g0, intervals[j][0])
                )
                j += 1
    n = len(gaps)
    out = {name: total[name] / n / 1e6 for name in GAP_PHASES}
    out["gap"] = sum(g1 - g0 for g0, g1 in gaps) / n / 1e6
    out["unnamed"] = out["gap"] - sum(out[name] for name in GAP_PHASES)
    return out


def gap_phase_ms(run, phase: str):
    split = gap_split(of(run))
    return None if split is None else split[phase]


# -- the fit job: its span tree ----------------------------------------------


#: Spans that only hold others: ``lease`` is the fit's hold of the
#: chip, so ``fit_init``, the epochs and ``checkpoint_save`` lie in it.
CONTAINERS = ("job", "lease")


def job_spans(record) -> list:
    """The window job's spans, each once: ``name``, ``start`` (epoch
    seconds), ``durationS``.  (``rest.spans`` walks the trace's flat
    list and its tree alike, so each span comes twice.)"""
    once = {
        (s["name"], s["start"], s["durationS"]): s
        for s in record.get("spans") or []
        if s.get("start") is not None and s.get("durationS") is not None
    }
    return list(once.values())


def span_seconds(record, names) -> float | None:
    """Summed duration of the window job's spans called one of
    ``names``; None where the program recorded none of them."""
    found = [
        s["durationS"] for s in job_spans(record) if s["name"] in names
    ]
    return sum(found) if found else None


def unnamed_seconds(record) -> float:
    """Of the ``job`` span (of each attempt), what no span inside it
    covers but the :data:`CONTAINERS`: the self time of ``job`` and of
    ``lease``."""
    spans = job_spans(record)
    out = 0.0
    for job in (s for s in spans if s["name"] == "job"):
        j0, j1 = job["start"], job["start"] + job["durationS"]
        covered, _ = trace._union(
            (max(c["start"], j0), min(c["start"] + c["durationS"], j1))
            for c in spans
            if c["name"] not in CONTAINERS
            and c["start"] < j1 and c["start"] + c["durationS"] > j0
        )
        out += job["durationS"] - covered
    return out


# -- the flash kernels, one by one ------------------------------------------


def flash_kernel_roofline(record, run, kernel: str):
    """Share of its roofline of ONE of the flash kernels over the
    traced job: the least time the chip could take for the kernel's two
    (T x T x head) matmuls a head (recomputed scores and dP never
    count) or for moving its tensors once, whichever is longer, over
    the summed device time of the custom calls named ``kernel``."""
    from lobench import counts

    job = record.get("job")
    spans = of(run)
    if not job or spans is None:
        return None
    spent = spans.kernels.get(kernel, 0.0)
    if spent <= 0:
        return None
    cp, batch, seq = run.cp, job["batch_size"], job["seq"]
    least, _bound = counts.roofline_seconds(
        counts.flash_train_flops(cp, batch, seq) / 3.0,
        FLASH_TENSORS[kernel] * batch * seq * cp["hidden_dim"] * 2.0,
        run.peaks,
    )
    steps = job["epochs"] * (job["rows"] // batch)
    return 100.0 * least * cp["num_layers"] * steps / spent
