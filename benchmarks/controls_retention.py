"""The controls of ``correct`` for a cell whose model keeps a recurrent
state, read on the chip at the cell's own size (PERF.md gives the
readings the limit was set from):

    python3 benchmarks/controls_retention.py --workload <name> --seeds 11 12 13 [--seconds 15] [--witness] [--break state_bf16]

A short window of the cell's own load runs first; then, at each
position of the sampled prompts and served tokens, the token that the
reference with int8 operands in every matmul puts first is read
against the float32 reference.  ``--witness`` reads the reference with
bfloat16 operands, the stated precision and nothing of the program,
the same way.  ``--break`` runs the PROGRAM with one fault underneath
(:data:`BREAKS`) and prints its own ``logit_gap`` and the harness's
``correct``: a limit that a break passes under is too loose.  Each
stand-in's reading goes through the comparison's own numbers against
the cell's limits (``correct_int8`` must read false).  Not part of a benchmark run;
``tests/test_retention_generate.py`` drives the same functions at tiny
widths."""

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.append(str(_HERE.parent))

from lobench import compare, loader, runner  # noqa: E402


def stand_in(run, quant: str) -> float:
    """``logit_gap`` of the tokens the reference in ``quant`` precision
    puts first, on the rows the run sampled.  The two sets of logits
    are never held at once (10 GB each at this vocabulary): the
    control's choices are read first, then the float32 reference."""
    import jax.numpy as jnp
    import numpy as np

    tokens, first, last = run.sample
    low = run.reference.reference_logits(run.seed, run.cp, tokens,
                                         quant=quant)
    _, best = compare._token_gaps(low, jnp.asarray(tokens), first, last)
    # the control's choice at p stands where the served token p+1 does
    chosen = np.asarray(jnp.roll(best, 1, axis=1))
    del low, best
    logits = run.reference.reference_logits(run.seed, run.cp, tokens)
    gaps, _ = compare._token_gaps(logits, jnp.asarray(chosen), first, last)
    return float(jnp.max(gaps))


def verdict(run, gap: float) -> bool:
    """What ``correct`` would read of a run whose served tokens lay
    ``gap`` under the reference's best, by the cell's own limits."""
    numbers = compare._numbers({"logit_gap": gap}, run.traffic["limits"])
    return all(c["value"] <= c["limit"] for c in numbers.values())


def _no_reset(real):
    """A seated slot not begun from zero."""
    import jax.numpy as jnp

    return lambda state, norm, fq, fk, v, g, live, fresh: real(
        state, norm, fq, fk, v, g, live, jnp.zeros_like(fresh))


def _no_gate(real):
    """The gate left out: ``g = 1``, nothing decays."""
    import jax.numpy as jnp

    return lambda state, norm, fq, fk, v, g, live, fresh: real(
        state, norm, fq, fk, v, jnp.ones_like(g), live, fresh)


def _state_bf16(real):
    """The state rounded to bfloat16 after every step."""
    import jax

    def step(*args):
        num, den, state, norm = real(*args)
        return num, den, *(
            jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
            for a in (state, norm)
        )

    return step


#: The faults a limit has to catch, each the program's
#: ``retention_step`` wrapped.
BREAKS = {"no_reset": _no_reset, "no_gate": _no_gate,
          "state_bf16": _state_bf16}


@contextlib.contextmanager
def broken(name: str):
    """The program with fault ``name`` underneath, its step programs
    built anew on both sides of the block."""
    from learningorchestra_tpu.ops import retention
    from learningorchestra_tpu.train import compile_cache

    real = retention.retention_step
    compile_cache.get_cache().clear()
    retention.retention_step = BREAKS[name](real)
    try:
        yield
    finally:
        retention.retention_step = real
        compile_cache.get_cache().clear()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--witness", action="store_true")
    parser.add_argument("--break", dest="fault", choices=sorted(BREAKS))
    args = parser.parse_args(argv)
    bench = loader.benchmark()
    for seed in args.seeds:
        one = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0
        )
        run = runner.Run(one, time.perf_counter(), bench)
        run.look_for_chip()
        with broken(args.fault) if args.fault \
                else contextlib.nullcontext():
            line = runner.execute(run)
        out = {
            "program": line["compared"]["logit_gap"]["value"],
            "limit": line["compared"]["logit_gap"]["limit"],
            # the harness's own verdict: false under a break
            "correct": line["correct"],
            "fault": args.fault,
            "failed": line["failed"], "attempted": line["attempted"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "memory_peak_bytes": line["device"].get("memory_peak_bytes"),
        }
        if not args.fault:
            for quant in ("int8", "bf16") if args.witness else ("int8",):
                gap = stand_in(run, quant)
                out[f"control_{quant}"] = gap
                # the stand-in in the engine's place, through the
                # comparison's own numbers: int8 must come out false
                out[f"correct_{quant}"] = verdict(run, gap)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
