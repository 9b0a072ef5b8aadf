#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the cell's chips:

- the program's numbers (``check.numbers``) on each seed: the lower
  readings;
- the control's: the reference in the program's place, computed one
  precision step below what the cell's traffic states (``control``);
- planted faults, in the reference put in the program's place: half of the
  batch left out, the mean taken over the rest; and on several chips the
  exchange between them left out, so that the optimizer gets one chip's
  share of the batch alone. A step that returns its state unchanged reads
  1 on both norm gaps (a zero moment and a zero change) and needs no run.

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds N] [--out .bench_out/calibrate.jsonl]

The control and the faults run on the first ``--control-seeds`` seeds
(default all), the program on every seed.

Each seed's readings are one JSON line of ``--out``; the summary (the
largest program reading and the smallest control or fault reading of each
number) is the last line of standard output.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=None)
    ap.add_argument("--out", default=str(ROOT / ".bench_out" / "calibrate.jsonl"))
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import catalog, check, harness, program, weights
    from benchmarks.chip.traffic import TokenStream

    bench = catalog.load_benchmark(ROOT)
    c = catalog.cell(args.workload, bench)
    devices = harness.devices_for(c["chips"])
    harness.use_compile_cache(ROOT)
    cfg, traffic = c["config"], c["traffic"]
    table = catalog.reference(cfg["reference"]).param_table(cfg)
    prog = program.build(cfg, traffic, devices)
    weights.check_layout(table, prog.engine.specs)
    tr = program.trainer(prog, None)
    faults = {"half_batch": traffic["global_batch"] // 2}
    if c["chips"] > 1:
        faults["no_exchange"] = traffic["global_batch"] // c["chips"]

    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    rows = []
    with open(args.out, "a") as fh:
        seeds = [int(s) for s in args.seeds.split(",")]
        n_control = len(seeds) if args.control_seeds is None else args.control_seeds
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            tr.data = TokenStream.for_traffic(traffic, cfg["vocab_size"], seed)
            state = weights.program_state(prog.engine.abstract_state(), table,
                                          seed)
            state, mine = harness.program_readings(tr, table, seed, traffic,
                                                   state)
            harness.free(state)
            del state
            ref = harness.reference_readings(c, table, seed, devices)
            row = dict(workload=args.workload, seed=seed,
                       program=check.numbers(mine, ref),
                       losses=dict(program=mine["losses"], reference=ref["losses"]))
            if i < n_control:
                ctl = harness.reference_readings(c, table, seed, devices,
                                                 precision=traffic["control"])
                row["control"] = check.numbers(ctl, ref)
                for name, keep in faults.items():
                    f = harness.reference_readings(c, table, seed, devices,
                                                   rows=keep)
                    row[name] = check.numbers(f, ref)
            row["seconds"] = time.perf_counter() - t0
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            print(json.dumps(row), flush=True)
            rows.append(row)
    summary = {}
    for k in check.NUMBERS:
        summary[k] = dict(
            lower=max(r["program"][k] for r in rows),
            **{f: min(r[f][k] for r in rows if f in r)
               for f in ["control", *faults] if any(f in r for r in rows)})
    print(json.dumps(dict(summary=summary, seconds=time.perf_counter() - T_START)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
