#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are listed in
BENCHMARK.json at the root of the checkout. The run needs an accelerator:
with no TPU (or fewer chips than the cell asks for) it exits 2 and prints
no result. On success the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and, with ``--trace 1``, ``breakdown``), then ``checks``: every number
compared, with its limit; the same numbers end standard error.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where
that is set, else ``.jax_cache/`` in the checkout. Traces of ``--trace 1``
runs are written under ``bench_out/`` in the checkout and removed once
read.
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
OUT = ROOT / "bench_out"
MIN_SECONDS = 1.0


def require_accelerator(chips: int) -> None:
    """Exit 2, printing no result, unless JAX runs on at least ``chips``
    TPU devices. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds < MIN_SECONDS:
        print(f"--seconds must be at least {MIN_SECONDS}", file=sys.stderr)
        return 2

    from bench import harness
    bench = harness.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if cell is None:
        print(f"no workload {args.workload!r}", file=sys.stderr)
        return 2
    require_accelerator(cell["chips"])
    out = OUT / "run"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True, exist_ok=True)
    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_process=T_PROCESS,
                           out_dir=out)
    except harness.NoWork as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(out, ignore_errors=True)
    if not harness.finite(line):
        print(f"no result: a number is not finite: {line}", file=sys.stderr)
        return 3
    checks = line["checks"]
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
