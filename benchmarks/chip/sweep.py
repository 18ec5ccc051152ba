"""Find a serving cell's knee: the same engine and weights, one window per
offered rate, in one process on the chip.

    python benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --seconds <s> --rates 10,15,20 [--out sweep.json]

For each rate it prints the window's tails, its throughput against the
offered load, and two signs of a growing backlog: the requests still
queued or in flight when the window closed, and the median time to first
token of the last quarter of arrivals over that of the first quarter.
The knee is the highest rate with neither.  It is run once, by hand,
when a cell is defined; the cell's traffic file records the result.
"""
import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import harness  # noqa: E402
import loadgen  # noqa: E402
import serve_cell  # noqa: E402


def backlog(wlog, seconds: float) -> dict:
    """Requests arrived but unfinished at the close, and the drift of
    time to first token across the window."""
    open_at_close = sum(1 for s in wlog.served
                        if not s.stamps or s.stamps[-1] > seconds)
    arrived = sorted(wlog.served, key=lambda s: s.planned.arrival_s)
    q = max(1, len(arrived) // 4)

    def med_ttft(xs):
        v = [s.stamps[0] - s.planned.arrival_s for s in xs if s.stamps]
        return harness.percentile(v, 50) if v else float("nan")
    return {"unfinished_at_close": open_at_close,
            "ttft_drift": med_ttft(arrived[-q:]) / med_ttft(arrived[:q])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=None,
                    help="seconds to drain after each window (default: the "
                    "traffic file's)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.check_devices(cell.chips)
    t0 = time.monotonic()
    prep = serve_cell.prepare(cell, args.seed, args.seconds)
    harness.log(f"[sweep] set-up {time.monotonic() - t0:.2f}s")
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"]["rate_per_s"] = rate
        plan = loadgen.schedule(traffic, args.seed, args.seconds,
                                vocab_size=cell.config["vocab_size"])
        # another rate makes other prompt lengths: warm their buckets
        serve_cell.warm_up(prep.engine, traffic["engine"], plan,
                           cell.config["vocab_size"], args.seed)
        drain = traffic["drain_s"] if args.drain is None else args.drain
        wlog = serve_cell.open_loop(prep.engine, plan, args.seconds,
                                    drain_s=float(drain))
        m = serve_cell.window_metrics(wlog)
        row = {"rate_per_s": rate, "attempted": m["attempted"],
               "failed": m["failed"],
               "offered_tok_per_s": loadgen.offered_tokens_per_s(traffic),
               **{k: m.get(k) for k in ("out_tok_per_s", "ttft_p50_ms",
                                        "ttft_p95_ms", "itl_p50_ms",
                                        "itl_p95_ms")},
               "lag_p95_ms": harness.percentile(m["lag_s"], 95) * 1e3,
               "drained_s": wlog.drained_s,
               "decode_steps": prep.engine.decode_steps,
               "peak_concurrency": prep.engine.peak_concurrency,
               **backlog(wlog, args.seconds)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    info = harness.device_info(devices)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "seconds": args.seconds, "device": info,
                      "rows": rows}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows,
                       "device": info}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
