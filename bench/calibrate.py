#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run): for each seed, one run of the cell, then the program's
numbers beside the control's (the reference computed in float8, the
precision below the configuration's bfloat16, put in the program's place)
and those of planted faults (half of each trained batch left out; one
served token altered), each judged by the cell's limits as the program's
are. All seeds run in one process, so programs compile once.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 51

Prints one JSON line per seed, then a summary line: for every number the
largest program reading and the smallest control and fault readings, and
whether the control and each fault ever came out correct. With
``--trace 1`` each run is also a traced run (its per-layer metrics and
breakdown are in its line).
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="directory for per-row gap details")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import require_accelerator
    require_accelerator(1)
    recs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        out = ROOT / "bench_out" / "calibrate"
        shutil.rmtree(out, ignore_errors=True)
        out.mkdir(parents=True)
        line = harness.run(args.workload, seed, args.seconds,
                           bool(args.trace), t_process=t0, out_dir=out,
                           calibrate=True)
        rows = line["calibration"].pop("rows", [])
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            (Path(args.dump) / f"rows_{seed}.json").write_text(
                json.dumps(rows))
        rec = {"seed": seed, "correct": line["correct"],
               "program": {k: v["value"] for k, v in line["checks"].items()},
               "calibration": line["calibration"],
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "device": line["device"],
               "breakdown": line.get("breakdown"),
               "attempted": line["attempted"], "failed": line["failed"],
               "wall_s": time.monotonic() - t0}
        print(json.dumps(rec), flush=True)
        recs.append(rec)
    summary = {"program_max": {}, "calibration_min": {}}
    for k in recs[0]["program"]:
        summary["program_max"][k] = max(r["program"][k] for r in recs)
    for k in recs[0]["calibration"]:
        vals = [r["calibration"][k] for r in recs]
        summary["calibration_min"][k] = (any(vals) if k.endswith("_correct")
                                         else min(vals))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
