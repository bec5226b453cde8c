"""Benchmark of the served gang-scheduling path on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json in this process, which holds the chip:
builds the cell's cluster and state from the seed, boots the scheduler
behind its HTTP server, warms every window shape the traffic uses,
measures for --seconds (with --trace 1: a few seconds under the
profiler), checks every answer against the plain reference, and prints
one JSON object as the last line of standard output. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu logs under /tmp by default; keep them in this run's TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import harness

    if not os.path.exists(os.path.join(harness.ROOT, "spark_scheduler_tpu")):
        print("run.py: the program under test is not beside the benchmark", file=sys.stderr)
        return 2
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except harness.NoChip as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 2
    checks = result["checks"]
    sys.stdout.flush()
    for key, c in checks.items():
        print(f"check {key} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
