#!/usr/bin/env python3
"""Reads the two ends a limit is set between, by hand, on the chip: for
each seed, in one process, a short run of the cell as ``run.py`` makes it
(the program's reading: how far its served tokens lie below the float32
reference's best) and, at the same prompts and tokens, the control's (the
reference computed in int8 in the program's place), each put through the
harness's one verdict (``harness.decide``) against the cell's own limits.
One JSON line a seed. The program has to come out correct and the control
not: exit code 5 where a control passed, 6 where the program failed.
``run.py`` never runs the control.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 --seconds 8

``--plain-seeds`` are further seeds read without the control (it doubles
the reference's time): the program's reading wants a dozen seeds, the
control's three.
"""

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="", help="comma-separated")
    parser.add_argument("--plain-seeds", default="", help="comma-separated")
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    sys.path.insert(0, REPO_ROOT)
    os.chdir(REPO_ROOT)
    from benchmark import harness

    bench = harness.read_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
    seeds = [(int(s), True) for s in args.seeds.split(",") if s]
    seeds += [(int(s), False) for s in args.plain_seeds.split(",") if s]
    code = 0
    for seed, control in seeds:
        try:
            line = harness.run_cell(
                repo_root=REPO_ROOT, bench_dir=BENCH_DIR, bench=bench,
                workload=args.workload, seed=seed, seconds=args.seconds,
                traced=False, t_process=time.perf_counter(), control=control)
        except harness.NoAccelerator as exc:
            print(f"control: {exc}", file=sys.stderr)
            return 3
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "attempted": line["attempted"], "failed": line["failed"],
            "program": {"correct": line["correct"],
                        "compared": line["compared"]},
            "control": line.get("control")}), flush=True)
        if not line["correct"]:
            print(f"control: seed {seed}: the program read incorrect",
                  file=sys.stderr)
            code = code or 6
        if control and line["control"]["correct"]:
            print(f"control: seed {seed}: the control passed every limit",
                  file=sys.stderr)
            code = 5
        gc.collect()
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
