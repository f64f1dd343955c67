#!/usr/bin/env python3
"""Finds a cell's knee once, by hand, on the chip: one process builds the
cell as ``run.py`` does, then runs a window at each of the given rates and
prints each window's end-to-end numbers as one JSON line. The rate a cell
is fixed at (about four fifths of the highest one sustained) goes into
its traffic file with the sweep that found it; ``run.py`` never searches.

    python3 benchmark/sweep.py --workload <name> --seed <n> --seconds <s> --rates 4,8,12
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", required=True,
                        help="sessions per second, comma-separated")
    args = parser.parse_args(argv)
    sys.path.insert(0, REPO_ROOT)
    os.chdir(REPO_ROOT)
    from benchmark import harness, load, traffic

    bench = harness.read_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
    try:
        ctx = harness.prepare(repo_root=REPO_ROOT, bench_dir=BENCH_DIR,
                              bench=bench, workload=args.workload,
                              seed=args.seed)
    except harness.NoAccelerator as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 3
    rates = [float(r) for r in args.rates.split(",")]
    plans = []
    for i, rate in enumerate(rates):
        mix = copy.deepcopy(ctx["mix"])
        mix["arrivals"]["sessions_per_s"] = rate
        plans.append(traffic.generate(mix, ctx["vocab"], args.seconds,
                                      args.seed + i))
    warm = harness._warmup_requests(ctx["serve"], ctx["mix"], ctx["vocab"],
                                    args.seed)
    windows = asyncio.run(harness._drive(
        ctx["system"], plans, warm, ctx["vocab"], False, "", ctx["compiles"],
        T_PROCESS))
    for rate, obs in zip(rates, windows, strict=True):
        recs = load.in_window(obs["records"], obs["t0"], args.seconds)
        e2e = harness.end_to_end(obs, args.seconds)
        e2e.pop("setup_s")
        print(json.dumps({
            "sessions_per_s": rate, "attempted": len(recs),
            "requests_per_s": len(recs) / args.seconds,
            "failed": sum(1 for r in recs if not r.ok),
            "drained_s": obs["drained_s"],
            "lateness_p99_ms": load.lateness_ms(obs["records"])["p99"],
            "compiled_in_window": ctx["compiles"].named_between(
                obs["marks"]["start"]["time"], obs["marks"]["end"]["time"]),
            **e2e}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
