"""Readings for the limits: the program's numbers and the control's, seed
by seed, in one process.

  python3 bench/control.py --workload deepseek-7b-kv.decode --seeds 1,2,3 --seconds 3

For each seed the cell is set up as ``run.py`` sets it up, driven through
a short window, and its output compared with the reference (the lower
reading); then the control — the reference computed one precision below
the configuration's, in the program's place — is compared the same way
(the upper reading).  The benchmark's own runs never run the control.
One JSON line per seed on stdout, then a summary line: for each number
the largest program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def readings(doc: dict, name: str, seed: int, seconds: float, parts=None) -> dict:
    import cells

    cell, config, mix, limits = parts or run.load_cell(doc, name)
    drv = cells.driver(config, mix, seed)
    drv.setup()
    drv.window(seconds)
    out = drv.outputs()
    program, _ = drv.check(out, limits)
    control, _ = drv.check(drv.control(out), limits)
    return {"seed": seed,
            "program": {k: v["value"] for k, v in program.items()},
            "control": {k: v["value"] for k, v in control.items()}}


def summary(rows: list[dict]) -> dict:
    keys = rows[0]["program"]
    return {k: {"program_max": max(r["program"][k] for r in rows),
                "control_min": min(r["control"][k] for r in rows)} for k in keys}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    with open(run.ROOT / "BENCHMARK.json") as fh:
        doc = json.load(fh)
    sys.path.insert(0, str(run.ROOT / "src"))
    try:
        run.on_tpu(1)
    except run.Refused as e:
        run.log(f"bench/control.py: {e}")
        return 2
    rows = []
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        rows.append(readings(doc, args.workload, int(s), args.seconds))
        run.log(f"seed {s}: {time.perf_counter() - t0:.1f} s")
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
