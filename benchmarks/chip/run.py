"""Run one cell of the chip benchmark and print its result line.

    python benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, limits and per-layer metric readers are files under
``benchmarks/chip`` found by their names.  The configuration's
``runner`` module runs it: set-up (weights or data from the seed, every
program compiled or read from the persistent cache, every shape warmed),
then a measured window of ``--seconds``, then the correctness check.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
with ``--trace 1``), and last of all ``compared``: each number the
check compared, beside its limit.  Those also end standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import harness  # noqa: E402
from harness import BenchError, log  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def run_cell(args: argparse.Namespace, *, devices=None,
             t_start: float = T_START, **where) -> dict:
    """Run the cell and return its result line.  ``devices`` skips the
    look for a chip (tests hand in the CPU device); ``where`` goes to
    :func:`harness.find_cell`."""
    cell = harness.find_cell(args.workload, **where)
    harness.enable_compile_cache()
    if devices is None:
        devices = harness.check_devices(cell.chips)
    harness.peaks_for(devices[0].device_kind)
    clock = harness.CompileClock()
    runner = importlib.import_module(cell.config["runner"])
    res = runner.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     t_start=t_start, clock=clock)
    line = harness.result_line(cell, res, trace=bool(args.trace))
    for c in res.checks:
        log(f"[compared] {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = run_cell(args)
    except BenchError as e:
        log(f"run.py: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
