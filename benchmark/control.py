"""Readings of the check on sound runs and under a control or fault, on
the chip, at the cell's own size, in one process.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \
        --seconds 5 --runs sound,fifo-off,answer-altered

Prints one JSON line per run: the run's name, the seed, `correct`, and
each number the check compares. The benchmark's own runs never run this.
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
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import faults
    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--runs", default="sound")
    args = ap.parse_args(argv)
    table = {"sound": ({}, None), **faults.CONTROLS, **faults.FAULTS}
    for run in args.runs.split(","):
        overrides, tamper = table[run]
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            try:
                res = harness.run_cell(
                    args.workload, seed, args.seconds, False, t_start=t0,
                    overrides=overrides, tamper=tamper,
                )
                line = {"run": run, "seed": seed, "correct": res["correct"],
                        **{k: v["value"] for k, v in res["checks"].items()}}
            except harness.NoChip:
                raise
            except Exception as exc:  # a run that crashes gives no number
                line = {"run": run, "seed": seed, "correct": False, "crashed": repr(exc)[:300]}
            print("READING " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
