#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once and prints one line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process loads, warms up, measures for ``--seconds``, compares what
the timed path served with the plain reference, and prints as the last
line of standard output one JSON object, checked against the contract
before it is printed. Everything else goes to standard error. Without a
TPU, with fewer chips than the cell asks for, or on a chip missing from
``benchmark/peaks.json``, it prints no result and exits with code 3.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, REPO_ROOT)
    os.chdir(REPO_ROOT)
    from benchmark import contract, harness

    bench = harness.read_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
    try:
        line = harness.run_cell(
            repo_root=REPO_ROOT, bench_dir=BENCH_DIR, bench=bench,
            workload=args.workload, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), t_process=T_PROCESS)
    except harness.NoAccelerator as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    try:
        contract.validate(line, bench, args.workload, bool(args.trace))
    except contract.ContractError as exc:
        print(json.dumps(line, default=repr), file=sys.stderr)
        print(f"benchmark: the line above is not the contract's: {exc}",
              file=sys.stderr)
        return 4
    sys.stdout.flush()
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the app's and gRPC's own threads may log while the interpreter winds
    # down; leave at once, so that nothing follows the last line
    os._exit(code)
