"""The controls of ``correct`` for a cell whose model keeps, in every
layer, K/V pages beside a Mamba-2 state and its convolution tail, read
on the chip at the cell's own size (PERF.md gives the readings the
limit was set from):

    python3 benchmarks/controls_hybrid.py --workload <name> --seeds 11 [--seconds 15] [--witness] [--break none no_reset ...]

A short window of the cell's own load runs first; then, at each
position of the sampled prompts and served tokens, the token that the
reference with int8 operands in every matmul puts first is read
against the float32 reference.  ``--witness`` reads the reference with
bfloat16 operands, the stated precision and nothing of the program,
the same way.  ``--break`` runs the PROGRAM with each named fault
underneath in turn (:data:`BREAKS`), and prints its
own ``logit_gap`` and the harness's ``correct``: a limit that a break
passes under is too loose.  Each stand-in's reading goes through the
comparison's own numbers against the cell's limits (``correct_int8``
must read false).  Not part of a benchmark run;
``benchmarks/tests/test_hybrid_generate.py`` drives the breaks at tiny
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

# The stand-in readings are the retention controls' own: the same
# comparison over the same sampled rows, one set of logits at a time.
from controls_retention import stand_in, verdict  # noqa: E402
from lobench import loader, runner  # noqa: E402


def _no_reset(real):
    """A seated slot's state not begun from zero."""
    import jax.numpy as jnp

    return lambda *args: real(*args[:-1], jnp.zeros_like(args[-1]))


def _state_bf16(real):
    """The state rounded to bfloat16 after every step."""
    import jax

    def step(*args):
        y, state = real(*args)
        return y, jax.lax.reduce_precision(
            state, exponent_bits=8, mantissa_bits=7)

    return step


def _tail_frozen(real):
    """The convolution's tail never shifted: every step convolves the
    new input with the tail the slot was seated with."""
    def step(tail, *args):
        out, _shifted = real(tail, *args)
        return out, tail

    return step


def _kv_unwritten(real):
    """Attention's K and V rows of the step never reach the pages (each
    position's are written as zeros, what a fresh pool holds where no
    row was written)."""
    import jax.numpy as jnp

    return lambda q, k, v, *rest: real(
        q, jnp.zeros_like(k), jnp.zeros_like(v), *rest)


#: The faults a limit has to catch: (module, function, wrapper).  A
#: tail not begun from zero is not among them: it reaches a request's
#: first three positions alone, under a convolution whose bias outweighs
#: its inputs six to one with seeded weights, and read 0.00027 on the
#: chip, a sound run's reading (PERF.md, section 4); tier-1 holds that
#: reset at tiny widths.
BREAKS = {
    "no_reset": ("learningorchestra_tpu.ops.ssm", "ssm_step", _no_reset),
    "state_bf16": ("learningorchestra_tpu.ops.ssm", "ssm_step",
                   _state_bf16),
    "tail_frozen": ("learningorchestra_tpu.ops.ssm", "conv_step",
                    _tail_frozen),
    "kv_unwritten": ("learningorchestra_tpu.ops.layers", "cached_attend",
                     _kv_unwritten),
}


@contextlib.contextmanager
def broken(name: str):
    """The program with fault ``name`` underneath, its step programs
    built anew on both sides of the block."""
    import importlib

    from learningorchestra_tpu.train import compile_cache

    where, attr, wrap = BREAKS[name]
    module = importlib.import_module(where)
    real = getattr(module, attr)
    compile_cache.get_cache().clear()
    setattr(module, attr, wrap(real))
    try:
        yield
    finally:
        setattr(module, attr, real)
        compile_cache.get_cache().clear()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--witness", action="store_true")
    # "none" is a sound run among the breaks (with its stand-ins).  At
    # this configuration a second run in one process can outgrow the
    # chip machine's 40 GiB of host memory: give each its own process
    parser.add_argument("--break", dest="faults", nargs="*",
                        default=["none"], choices=["none", *sorted(BREAKS)])
    args = parser.parse_args(argv)
    bench = loader.benchmark()
    for fault in args.faults:
        fault = None if fault == "none" else fault
        for seed in args.seeds:
            one = argparse.Namespace(workload=args.workload, seed=seed,
                                     seconds=args.seconds, trace=0)
            run = runner.Run(one, time.perf_counter(), bench)
            run.look_for_chip()
            with broken(fault) if fault else contextlib.nullcontext():
                line = runner.execute(run)
            out = {
                "program": line["compared"]["logit_gap"]["value"],
                "limit": line["compared"]["logit_gap"]["limit"],
                # the harness's own verdict: false under a break
                "correct": line["correct"],
                "fault": fault,
                "failed": line["failed"], "attempted": line["attempted"],
                "metrics": {k: v["value"]
                            for k, v in line["metrics"].items()},
                "memory_peak_bytes": line["device"].get(
                    "memory_peak_bytes"),
            }
            if not fault:
                for quant in ("int8", "bf16") if args.witness \
                        else ("int8",):
                    gap = stand_in(run, quant)
                    out[f"control_{quant}"] = gap
                    # the stand-in in the engine's place, through the
                    # comparison's own numbers: int8 must come out false
                    out[f"correct_{quant}"] = verdict(run, gap)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              **out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
