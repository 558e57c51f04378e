"""The control of ``correct`` for a ``closed_loop_block_generate`` cell,
read on the chip at the cell's own size (PERF.md gives the readings the
limits were set from):

    python3 benchmarks/controls_blocks.py --workload <name> --seeds 11 12 13 [--seconds 15]

A short window of the cell's own load runs first; then, at the very
states the sampled requests' blocks went through, the reference with
int8 operands in every matmul (router and experts included) chooses
positions and tokens in the engine's place, and its choices are read
against the float32 reference.  ``--witness`` reads the reference with
bfloat16 operands, the stated precision, the same way.  Not part of a
benchmark run; ``tests/test_block_generate.py`` drives the same
function at tiny widths."""

import argparse
import json
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.append(str(_HERE.parent))

from lobench import compare_blocks, loader, runner  # noqa: E402


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
            "program": {k: c["value"] for k, c in line["compared"].items()},
            "failed": line["failed"], "attempted": line["attempted"],
        }
        for quant in ("int8", "bf16") if args.witness else ("int8",):
            out[f"control_{quant}"] = compare_blocks.numbers(
                run.reference, run.seed, run.cp, run.traffic, run.sample,
                quant=quant,
            )
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
