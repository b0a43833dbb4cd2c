"""Readings that the limits of a cell's checks are set from.  Run by hand on
the chip, never by a benchmark run:

    python3 bench/control.py --workload <cell> --seeds 3001-3012 --faults 3 \
        --out <file.jsonl>

For every seed, in one process: build the cell's program from the seed,
let it make its first updates through the timed path, stop it, free it,
and read the compared numbers of the program against the reference (the
lower readings).  For the first ``--faults`` seeds, also read them for
the control (the reference computed at float8, put in the program's
place) and for each fault the cell can have, planted in the reference put
in the program's place: half of the batch left out, the exchange between
learner chips left out (cells with several learners), a token altered
after its log-prob was recorded, the state left unchanged, every update's
sign flipped (the upper readings).  Writes one JSON line per seed and, at
the end, the summary: for each number the largest lower reading, the
smallest reading of the control and of each fault, and a limit set
between them.
"""

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import common  # noqa: E402

NUMBERS = ("logp_gap", "loss_gap", "grad_gap", "change_gap",
           "grad_gap_median", "change_gap_median", "signed_change_gap_median")


def seeds_arg(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def fault_variants(cell) -> list[str]:
    v = ["control", "half_batch", "altered_token", "unchanged", "sign_flip"]
    if cell.chips > 1:
        v.insert(2, "no_exchange")
    return v


def readings_for_seed(cell, seed: int, devices, variants) -> dict:
    drv = cell.driver
    t0 = time.perf_counter()
    s = drv.Session(cell, seed, devices)
    s.probe.wait_for(drv.CAPTURED + 1, s.alive)
    s.stop()
    probe = s.probe
    del s
    gc.collect()
    t1 = time.perf_counter()
    out = drv.reference_readings(cell, seed, probe, ["program"] + variants,
                                 detail=True)
    common.log(f"seed {seed}: program {t1 - t0:.1f} s, reference and "
               f"{len(variants)} variants {time.perf_counter() - t1:.1f} s")
    return out


def summarize(rows: list[dict]) -> dict:
    """Lower reading: the largest of the program's.  Upper: the smallest of
    the control's where that is 3x the lower or more, and of each fault's
    where that is 10x the lower or more (a state left unchanged: 3x).  The
    limit lies between, two thirds of the way up in log scale."""
    out = {}
    for n in NUMBERS:
        lower = max(r["program"][n] for r in rows)
        per = {}
        for v in {k for r in rows for k in r} - {"program", "seed", "detail"}:
            vals = [r[v][n] for r in rows if v in r]
            per[v] = min(vals) if vals else None
        need = {"control": 3.0, "unchanged": 3.0}
        upper_from = {
            v: x for v, x in per.items()
            if x is not None and x >= need.get(v, 10.0) * lower
        }
        upper = min(upper_from.values()) if upper_from else None
        limit = None
        if upper is not None and lower > 0:
            limit = lower * (upper / lower) ** (2.0 / 3.0)
        out[n] = {"lower": lower, "upper": upper, "by": upper_from,
                  "all": per, "limit": limit}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="read the control and faults on this many seeds")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    cell = common.Cell(args.workload)
    devices = common.require_devices(cell.chips)
    common.enable_compile_cache()
    rows = []
    with open(args.out, "a") as f:
        for i, seed in enumerate(args.seeds):
            variants = fault_variants(cell) if i < args.faults else []
            r = readings_for_seed(cell, seed, devices, variants)
            r["seed"] = seed
            rows.append(r)
            f.write(json.dumps(r) + "\n")
            f.flush()
            print(json.dumps({k: v for k, v in r.items() if k != "detail"}),
                  flush=True)
    summary = summarize(rows)
    for n, s in summary.items():
        lim = s["limit"]
        print(f"{n}: lower {s['lower']!r} upper {s['upper']!r} limit "
              f"{lim if lim is None else float(f'{lim:.3g}')!r} "
              f"(control and faults: {s['all']})", flush=True)
    print(json.dumps({"summary": summary}), flush=True)
    if any(not math.isfinite(r["program"][n]) for r in rows for n in NUMBERS):
        sys.exit(1)


if __name__ == "__main__":
    main()
