"""Run every BASELINE config bench in its own process; collect the JSON lines.

Usage: python bench/run_all.py [--out chiprun_out/bench_suite.json]
Each config runs in a fresh subprocess so env overrides and device state
never leak between configs, and so each in turn is the one process that
holds the chip: this parent never imports jax. It needs a TPU (a first
child checks, and nothing runs without one). A config failure is recorded
and the rest still run, but the suite then exits non-zero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# (script, extra env) — per-config env keeps optional arms on in the suite
# runs even when a config's own defaults would skip them under a tighter
# budget (config4 phase E: the adaptive-scheduler fixed-vs-adaptive A/B;
# phase F: the tiered-KV-cache offload-on-vs-off A/B; phase G: the
# resilience fault-vs-clean A/B; phase H: the flight-recorder stall
# breakdown + recorder-overhead A/B; phase I: the speculation x
# KV-precision grid; phase J: the disaggregated prefill/decode A/B;
# phase M: the traffic-capture & replay arm — capture a mixed window,
# replay at 1x/4x, digest identity + capture overhead pct; phase N: the
# fused-decode-window single-step-vs-fused A/B (steady tok/s, launch
# phase share, TTFT/TPOT percentiles, greedy token identity); phase O:
# the pipelined-serving-loop double-buffered-dispatch A/B (steady
# tok/s, host_idle_estimate, greedy token identity); phase P: the
# self-tuning arm — replay-driven config search over the committed
# bench/ bundle (scoreboard, winner, lift vs default) + the winner
# shadow-canaried on a live pool (verdict, balanced canary ledger);
# config7's SP arm: sequence-parallel prefill TTFT/TPOT vs context
# length with the greedy token-identity verdict)
CONFIGS = [
    ("config1_echo.py", {}),
    ("config2_mnist.py", {}),
    ("config3_bert.py", {}),
    ("config4_llama.py", {"BENCH_SCHED_ARM": "1", "BENCH_OFFLOAD_ARM": "1",
                          "BENCH_FAULT_ARM": "1", "BENCH_STALL_ARM": "1",
                          "BENCH_SPEC_ARM": "1", "BENCH_DISAGG_ARM": "1",
                          "BENCH_ELASTIC_ARM": "1",
                          "BENCH_GOODPUT_ARM": "1",
                          "BENCH_REPLAY_ARM": "1",
                          "BENCH_WINDOW_ARM": "1",
                          "BENCH_PIPELINE_ARM": "1",
                          "BENCH_TUNE_ARM": "1"}),
    ("config5_sdxl.py", {}),
    ("config6_compute.py", {}),
    ("config7_longcontext.py", {"BENCH_SP_ARM": "1"}),
    ("config8_speculative.py", {}),
]


_CHIP_CHECK = (
    "import jax\n"
    "d = jax.devices()[0]\n"
    "if d.platform != 'tpu':\n"
    "    raise SystemExit(f'the suite measures a TPU, jax found "
    "{d.platform!r}')\n"
    "print(d.device_kind)\n"
)


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(os.path.dirname(here), "chiprun_out",
                            "bench_suite.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]

    check = subprocess.run([sys.executable, "-c", _CHIP_CHECK],
                           capture_output=True, text=True, timeout=300)
    if check.returncode:
        print(check.stderr[-1500:], file=sys.stderr)
        return 1
    print(f"device: {check.stdout.strip()}", flush=True)

    results = []
    for name, extra_env in CONFIGS:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, os.path.join(here, name)],
            capture_output=True, text=True, timeout=1200, cwd=here,
            env={**os.environ, **extra_env},
        )
        parsed = None
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                parsed = json.loads(line)
                break
            except (json.JSONDecodeError, ValueError):
                continue
        results.append({
            "config": name,
            "rc": proc.returncode,
            "wall_s": round(time.time() - t0, 1),
            "result": parsed,
            "stderr_tail": proc.stderr[-1500:] if proc.returncode else "",
        })
        status = "ok" if proc.returncode == 0 and parsed else "FAIL"
        print(f"[{status}] {name}: {json.dumps(parsed) if parsed else proc.stderr[-300:]}",
              flush=True)

    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}")
    failed = [r["config"] for r in results if r["rc"] or not r["result"]]
    if failed:
        print(f"failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
