"""Readings for a serving cell's correctness limit: the program's number
and the control's, seed by seed, in one process on the chip.

    python benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,3 --seconds <s> [--out calibrate.json]

For each seed it runs the cell as a benchmark run does (weights and
requests from the seed, the cell's traffic at its rate for ``--seconds``,
drained), then reads, on the same sampled requests, the widest gap by
which a served token's logit lies below the float32 reference's best
(the program's reading), and the same of the token the control, the
reference in float8, puts first (the control's reading).  The limit in
``limits/<workload>.json`` is set between the largest program reading
and the smallest control reading.  Benchmark runs never run the control.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import harness  # noqa: E402
import serve_cell  # noqa: E402


def readings(cell: harness.Cell, seed: int, seconds: float) -> dict:
    prep = serve_cell.prepare(cell, seed, seconds)
    wlog = serve_cell.open_loop(prep.engine, prep.plan, seconds,
                                drain_s=float(cell.traffic["drain_s"]))
    m = serve_cell.window_metrics(wlog)
    checked = serve_cell.pick_checked(
        wlog.served, int(cell.traffic["check"]["requests"]), seed)
    prep.engine.reset()
    del prep.engine
    gc.collect()
    length = int(cell.traffic["engine"]["cache_len"])
    prog = serve_cell.token_gaps(prep.ref, cell.config, prep.params,
                                 checked, length)
    ctrl = serve_cell.token_gaps(prep.ref, cell.config, prep.params,
                                 checked, length, control=True)
    return {"seed": seed, "attempted": m["attempted"], "failed": m["failed"],
            "program": prog, "control": ctrl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.check_devices(cell.chips)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = readings(cell, seed, args.seconds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {
        "workload": cell.name, "seconds": args.seconds,
        "device": harness.device_info(devices),
        "program_max": max(r["program"]["max"] for r in rows),
        "control_min": min(r["control"]["max"] for r in rows),
        "rows": rows}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
