#!/usr/bin/env python3
"""Read, on the chip, the numbers a cell's limits of `correct` are set from:
over ``--seeds`` seeds, what sound runs of the program give against the plain
reference, and what the control gives (the reference put in the program's
place, one precision lower). One process, one JSON line a seed; PERF.md keeps
the readings and the limits chosen between them.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12

Training cells need no measured window.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _train(ctx, control, program=True):
    from benchmark import check_train
    from benchmark.kinds import train_steps as kind

    cell, traffic = ctx.cell, ctx.cell.traffic
    batches = cell.family.make_batches(
        cell.config, traffic, traffic["per_chip_batch"] * cell.chips,
        ctx.seed, traffic["check_steps"],
    )
    reference = kind.reference_numbers(cell, batches, ctx.seed,
                                       ctx.devices[0])
    out = {}
    leaves = {"reference": reference}
    if control:
        lower = kind.reference_numbers(
            cell, batches, ctx.seed, ctx.devices[0],
            precision=cell.config["train"]["control_precision"],
        )
        out["control"] = check_train.compare(lower, reference)
        out["control_candidates"] = check_train.candidates(lower, reference)
        leaves["control"] = lower
    if program:
        loop, fresh = kind.build(ctx, batches)
        numbers = kind._program_numbers(cell, loop, fresh)
        out["program"] = check_train.compare(numbers, reference)
        out["program_candidates"] = check_train.candidates(numbers, reference)
        leaves["program"] = numbers
    out["reference_losses"] = reference["losses"]
    # the per-leaf norms go to the log file only: any other statistic of
    # them can then be read without another chip run
    out["leaves"] = {
        side: {k: [float(x) for x in v] for k, v in numbers.items()}
        for side, numbers in leaves.items()
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_147_483_659)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="how many of the seeds also read the control "
                         "(default: all)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control-only", action="store_true",
                    help="read only the control (the reference one precision "
                         "lower, against the reference): it runs on one chip, "
                         "whatever the cell asks for")
    args = ap.parse_args(argv)

    from benchmark import manifest, run

    cell = manifest.Cell(manifest.load_manifest(), args.workload,
                         rehearse=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}"
        )
    else:
        run._compile_cache()
    devices = run._devices(cell, args.rehearse_cpu,
                           chips=1 if args.control_only else None)
    n_control = args.seeds if args.control_seeds is None else args.control_seeds
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell.name + ".jsonl"), "a") as log:
        for i in range(args.seeds):
            args.seed = args.first_seed + 7919 * i
            args.trace, args.seconds = 0, 0.0
            ctx = run.Context(cell, args, devices)
            t = time.perf_counter()
            if args.control_only:
                row = _train(ctx, control=True, program=False)
            else:
                row = _train(ctx, control=i < n_control)
            row.update(seed=args.seed, cell=cell.name,
                       device=devices[0].device_kind,
                       seconds=time.perf_counter() - t)
            log.write(json.dumps(row) + "\n")
            row.pop("leaves", None)
            print(json.dumps(row), flush=True)
            log.flush()
            del ctx
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
