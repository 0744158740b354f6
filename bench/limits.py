#!/usr/bin/env python3
"""bench/limits.py — the two readings a limit of `correct` is set between.

    python3 bench/limits.py --workload <cell> --seeds 101 102 103 [--seconds 1]

Not part of a benchmark run. For each seed, at the cell's own size: generate
the data, run the cell's timed path in a child exactly as bench/run.py does
(first round, warm-up, a short window), and compare every answer with the
float64 reference — the program's reading (the lower one). Then put the
control in the program's place: the same reference computed in float32, the
precision below the one the configuration states — the control's reading (the
upper one). The control's answers are written where the child wrote the
program's, one for each answer the child gave, and go through the same
`run.judge`: `correct` has to come out false for them, or this exits 1. One
JSON line a seed, then one line with the largest program reading and the
smallest control reading. PERF.md records what the limits in
bench/configs/*.json were set from.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def put_in_place(out_dir: str, control_dir: str, answers: dict) -> None:
    """For every answer the child wrote under <out_dir>/results, the
    control's answer to the same query under <control_dir>/results."""
    import pyarrow as pa

    os.makedirs(os.path.join(control_dir, "results"))
    for path in glob.glob(os.path.join(out_dir, "results", "*.arrow")):
        name = os.path.basename(path)
        table = pa.Table.from_pandas(answers[name[:-len(".arrow")].split("_", 1)[1]],
                                     preserve_index=False)
        with pa.OSFile(os.path.join(control_dir, "results", name), "wb") as f:
            with pa.ipc.new_file(f, table.schema) as w:
                w.write_table(table)


def main(argv=None, run_child=run.spawn_child) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", type=float, metavar="SCALE", default=None)
    args = ap.parse_args(argv)

    _, cell, config, traffic = run.find_cell(args.workload)
    generator = importlib.import_module(f"lib.generator_{config['generator']}")
    scale = config["scale"] if args.rehearse is None else args.rehearse
    program, control, sound = [], [], True
    for seed in args.seeds:
        data_dir, out_dir = run.fresh_dirs(config, seed, f"limits_{cell['name']}_seed{seed}")
        try:
            t0 = time.time()
            generator.generate(data_dir, config, scale, seed)
            rc = run_child(run.child_args(cell, config, data_dir, out_dir, args.seconds,
                                                0, args.rehearse is not None))
            if rc != 0:
                return rc
            attempted = len(run.load_json(os.path.join(out_dir, "cell.json"))["executions"])
            wants = generator.answers(data_dir, config, traffic["queries"])
            checks, correct = run.judge(out_dir, wants, config["limits"], generator, attempted)
            lower = {k: c["value"] for k, c in checks.items()}
            control_dir = os.path.join(out_dir, "control")
            put_in_place(out_dir, control_dir,
                         generator.answers(data_dir, config, traffic["queries"], "float32"))
            checks, control_correct = run.judge(control_dir, wants, config["limits"], generator,
                                                attempted)
            upper = {k: c["value"] for k, c in checks.items()}
            run.emit({"seed": seed, "attempted": attempted, "program": lower,
                      "program_correct": correct, "control": upper,
                      "control_correct": control_correct, "seconds": time.time() - t0})
            sound = sound and correct and not control_correct
            program.append(lower)
            control.append(upper)
        finally:
            shutil.rmtree(data_dir, ignore_errors=True)
            shutil.rmtree(out_dir, ignore_errors=True)
    run.emit({"workload": cell["name"], "seeds": args.seeds,
              "program_largest": {k: max(p[k] for p in program) for k in program[0]},
              "control_smallest": {k: min(c[k] for c in control) for k in control[0]},
              "limits_now": config["limits"],
              "program_correct_and_control_not_on_every_seed": sound})
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
