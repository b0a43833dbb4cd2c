"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: refuse anything but the TPU chips the cell asks for; keep the
program's compile cache in the checkout; build the program and its seeded
weights and warm up the cell's own shapes (set-up); measure for
``--seconds``; with ``--trace 1``, trace a further short window and reduce
it to the cell's per-layer metrics; stop the program, free its state, and
check what the timed path produced against the plain reference.  The last
lines of standard error give each compared number beside its limit; the
last line of standard output is one JSON object.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``bench/workloads/<cell>.json``, ``bench/configs/<config>.{json,py}``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/drivers/<driver>.py``.  The configuration's module builds the
program's parts (its ``program_parts``), so a new configuration, of any
model family, is new files and no edit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import common  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="with --trace 1, also keep the reduced trace as "
                         "JSON (gzipped where the name ends in .gz)")
    args = ap.parse_args(argv)

    cell = common.Cell(args.workload)
    devices = common.require_devices(cell.chips)
    common.enable_compile_cache()
    result, checks = cell.driver.run(
        cell, args.seed, args.seconds, bool(args.trace), devices, T_START,
        trace_out=args.trace_out)
    common.emit(result, checks)


if __name__ == "__main__":
    main()
