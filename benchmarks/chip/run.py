#!/usr/bin/env python3
"""The benchmark's command: one run of one cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. Prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its per-layer
metrics), ``device`` and, traced, ``breakdown``; last comes ``check``, each
number that decided ``correct`` beside its limit, which also ends standard
error. With no TPU, or fewer chips than the cell asks for, or without the
program's sources beside it, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"the program's sources (src/repro) are not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    try:
        bench = harness.catalog.load_benchmark(ROOT)
        chips = harness.catalog.cell(args.workload, bench)["chips"]
        harness.devices_for(chips)
    except (harness.NoChip, harness.catalog.CellError) as e:
        print(str(e), file=sys.stderr)
        return 3
    harness.use_compile_cache(ROOT)

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START, bench=bench,
                           trace_dir=ROOT / ".bench_out" / "trace" / args.workload)
    for k, v in out["check"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
