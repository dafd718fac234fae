"""One run of one cell: load, warm up, measure, check, print one line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result. Everything else a run has
to say goes to standard error or under ``perfbench_out/``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, allow_cpu: bool = False) -> dict:
    """Drive one cell and return its line. ``allow_cpu`` is for the tests
    of the harness alone: the command never passes it."""
    harness.set_compile_cache()
    import jax

    devices = (jax.devices()[:cell["chips"]] if allow_cpu
               else harness.require_chips(cell["chips"]))
    result = harness.driver(cell["config"]["driver"]).run(
        cell, seed=seed, seconds=seconds, trace=trace, devices=devices,
        t_start=t_start)
    line = harness.build_line(cell, trace, result)
    faults = harness.line_faults(line, cell, trace)
    if faults:
        raise SystemExit("perfbench: the result line is malformed: "
                         + "; ".join(faults))
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    _T_START)
    harness.print_checks(line["checks"])
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
