"""The controls of ``correct``, read on the chip at each cell's own size
(PERF.md gives the readings the limits were set from):

    python3 benchmarks/controls.py --workload <name> --seeds 11 12 13 [--seconds 15]

For a ``fit_job`` cell nothing of the program runs: the reference in the
control's precision (int8 operands), and the reference with half of each
batch left out, stand in the program's place against the reference
(``--program`` reads the program itself instead: set-up through the warm
job on every seed in one process, no window; ``--witness`` reads the
reference with bfloat16 operands, the stated precision, in its place).  For
a ``closed_loop_generate`` cell a short window of the cell's own load
runs first; then, at each position of the sampled prompts and served
tokens, the token that the control's precision puts first is read
against the reference.  Not part of a benchmark run; the tests under
``tests/`` drive the same functions at tiny widths."""

import argparse
import json
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.append(str(_HERE.parent))

from lobench import compare, loader, runner  # noqa: E402
from lobench.kinds import fit_job  # noqa: E402


def fit_stand_in(run, *, quant=None, fault=None, steps=False) -> dict:
    """The numbers of ``fit_epoch`` with the reference, in ``quant``
    precision or with ``fault`` planted, in the program's place; with
    ``steps`` also each step's loss on both sides."""
    cp, module = run.cp, run.reference
    tokens, labels = fit_job.make_rows(run.seed, run.traffic, cp)
    kw = dict(batch=run.traffic["batch_size"], lr=cp["learning_rate"],
              model_seed=cp["seed"])
    ref = module.reference_epoch(run.seed, cp, tokens, labels, **kw)
    low = module.reference_epoch(
        run.seed, cp, tokens, labels, quant=quant, fault=fault, **kw
    )
    numbers = compare.fit_numbers(
        module, cp, ref, module.program_params(low["end"], cp),
        module.program_params(low["nu"], cp), low["loss"],
    )
    if steps:
        numbers["step_losses"] = [low["step_losses"], ref["step_losses"]]
    return numbers


def fit_program(run) -> dict:
    """The program's own readings without a window: set-up as a run
    makes it, through the warm job, then the comparison."""
    try:
        state = fit_job.set_up(run)
        numbers = fit_job.compared(run, state, None, [], steps_expected=None)
    finally:
        if run.server is not None:
            run.server.shutdown()
    return {k: v["value"] for k, v in numbers.items()}


def generate_control(run, quant: str = "int8") -> dict:
    """Run the cell's short window, then read the program's widest gap
    and the control's on the same sampled rows."""
    line = runner.execute(run)
    tokens, first, last = run.sample
    return {
        "program": line["compared"]["logit_gap"]["value"],
        "control": compare.served_gap(
            run.reference, run.seed, run.cp, tokens, first, last,
            quant=quant, of_control=True,
        ),
        "failed": line["failed"], "attempted": line["attempted"],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--program", action="store_true",
                        help="fit cells: the program's readings instead")
    parser.add_argument("--witness", action="store_true",
                        help="fit cells: the reference in the stated "
                        "precision (bf16 operands) in the program's place")
    args = parser.parse_args(argv)
    bench = loader.benchmark()
    for seed in args.seeds:
        one = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0
        )
        run = runner.Run(one, time.perf_counter(), bench)
        run.look_for_chip()
        if run.traffic["kind"] == "fit_job" and args.program:
            out = {"program": fit_program(run)}
        elif run.traffic["kind"] == "fit_job" and args.witness:
            out = {"witness_bf16": fit_stand_in(run, quant="bf16",
                                                steps=True)}
        elif run.traffic["kind"] == "fit_job":
            out = {
                "control_int8": fit_stand_in(run, quant="int8"),
                "fault_half_batch": fit_stand_in(run, fault="half_batch"),
            }
        else:
            out = generate_control(run)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
