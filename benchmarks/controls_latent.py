"""The control of ``correct`` for a ``closed_loop_generate_bf16`` cell,
read on the chip at the cell's own size (PERF.md gives the readings the
limit was set from):

    python3 benchmarks/controls_latent.py --workload <name> --seeds 11 12 13 [--seconds 15] [--witness]

A short window of the cell's own load runs first; then, at each
position of the sampled prompts and served tokens, the token that the
reference with int8 operands in every matmul (latent projections,
router and experts included) puts first is read against the float32
reference.  ``--witness`` reads the reference with bfloat16 operands,
the stated precision and nothing of the program, the same way.  Not
part of a benchmark run; ``tests/test_latent_generate.py`` drives the
same function at tiny widths."""

import argparse
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
    puts first, on the rows the run sampled."""
    tokens, first, last = run.sample
    return compare.served_gap(
        run.reference, run.seed, run.cp, tokens, first, last,
        quant=quant, of_control=True,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--witness", action="store_true")
    args = parser.parse_args(argv)
    bench = loader.benchmark()
    for seed in args.seeds:
        one = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0
        )
        run = runner.Run(one, time.perf_counter(), bench)
        run.look_for_chip()
        line = runner.execute(run)
        out = {
            "program": line["compared"]["logit_gap"]["value"],
            "failed": line["failed"], "attempted": line["attempted"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "memory_peak_bytes": line["device"].get("memory_peak_bytes"),
        }
        for quant in ("int8", "bf16") if args.witness else ("int8",):
            out[f"control_{quant}"] = stand_in(run, quant)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
