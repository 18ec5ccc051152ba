"""Benchmark harness: one function per paper table + kernel/roofline benches.

Prints ``name,us_per_call,derived`` CSV (harness contract).  Full-size runs:
``python -m benchmarks.run --full``; default sizes finish on the CPU box in
a few minutes.
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale NAS settings (hours)")
    ap.add_argument("--skip-nas", action="store_true",
                    help="only kernel + roofline benches")
    ap.add_argument("--json", action="store_true",
                    help="also write machine-readable per-bench results "
                         "(BENCH_<name>.json) for perf-trajectory tracking")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []
    t0 = time.time()

    from benchmarks import (
        fault_bench,
        kernel_bench,
        multi_platform_bench,
        nas_loop_bench,
        pipeline_bench,
        population_eval_bench,
        roofline_table,
        router_bench,
        serve_bench,
        train_bench,
    )
    kernel_rows = kernel_bench.run(log=lambda *a: print(*a, file=sys.stderr))
    rows += kernel_rows
    if args.json:
        kernel_bench.write_json(kernel_rows, "BENCH_kernels.json")
        print("# wrote BENCH_kernels.json", file=sys.stderr)
    rows += population_eval_bench.run(
        log=lambda *a: print(*a, file=sys.stderr))
    multi_platform_rows = multi_platform_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full)
    rows += multi_platform_rows
    if args.json:
        multi_platform_bench.write_json(multi_platform_rows,
                                        "BENCH_multi_platform.json")
        print("# wrote BENCH_multi_platform.json", file=sys.stderr)
    nas_loop_rows = nas_loop_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full)
    rows += nas_loop_rows
    if args.json:
        nas_loop_bench.write_json(nas_loop_rows, "BENCH_nas_loop.json")
        print("# wrote BENCH_nas_loop.json", file=sys.stderr)
    train_loop_rows = train_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full)
    rows += train_loop_rows
    if args.json:
        train_bench.write_json(train_loop_rows, "BENCH_train_loop.json")
        print("# wrote BENCH_train_loop.json", file=sys.stderr)
    # in-process: one process holds the chip, so no bench runs as a child.
    # Here the search shards over the devices this process sees (one on
    # the CPU unless XLA_FLAGS forces more before the run starts).
    pipeline_rows, pipeline_summary = pipeline_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full)
    rows += pipeline_rows
    if args.json:
        pipeline_bench.write_json(pipeline_rows, pipeline_summary,
                                  "BENCH_pipeline.json")
        print("# wrote BENCH_pipeline.json", file=sys.stderr)
    fault_rows, fault_summary = fault_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full)
    rows += fault_rows
    if args.json:
        fault_bench.write_json(fault_rows, fault_summary,
                               "BENCH_faults.json")
        print("# wrote BENCH_faults.json", file=sys.stderr)
    serve_rows, serve_summary = serve_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full,
        n_requests=64 if args.full else 32)
    rows += serve_rows
    if args.json:
        serve_bench.write_json(serve_rows, serve_summary, "BENCH_serve.json")
        print("# wrote BENCH_serve.json", file=sys.stderr)
    router_rows, router_summary = router_bench.run(
        log=lambda *a: print(*a, file=sys.stderr), smoke=not args.full)
    rows += router_rows
    if args.json:
        router_bench.write_json(router_rows, router_summary,
                                "BENCH_router.json")
        print("# wrote BENCH_router.json", file=sys.stderr)
    rows += roofline_table.run(log=lambda *a: print(*a, file=sys.stderr))
    roofline_table.write_markdown(log=lambda *a: print(*a, file=sys.stderr))

    if not args.skip_nas:
        from benchmarks import table1_objectives, table2_domains
        gens = 12 if args.full else 3
        samples = 1600 if args.full else 240
        steps = 300 if args.full else 60

        t = time.time()
        t1 = table1_objectives.run(generations=gens, samples=samples,
                                   train_steps=steps,
                                   log=lambda *a: print(*a, file=sys.stderr))
        for r in t1:
            rows.append({
                "name": f"table1:{r['nas_objective']}:{r['impl_strategy']}",
                "us_per_call": (time.time() - t) * 1e6 / max(len(t1), 1),
                "derived": (f"thr={r['throughput_sps']:.3g}sps "
                            f"P={r['p_total_w']:.2f}W "
                            f"E={r['e_total_uj']:.3g}uJ "
                            f"params={r['params']}"),
            })
        for claim, ok in table1_objectives.validate(t1).items():
            rows.append({"name": f"table1_claim:{claim}",
                         "us_per_call": 0.0, "derived": str(ok)})

        t = time.time()
        t2 = table2_domains.run(generations=gens, samples=samples,
                                train_steps=steps,
                                log=lambda *a: print(*a, file=sys.stderr))
        for r in t2:
            rows.append({
                "name": f"table2:{r['device'].split(' (')[0]}",
                "us_per_call": (time.time() - t) * 1e6 / max(len(t2), 1),
                "derived": (f"f={r['freq_mhz']:.0f}MHz batch={r['batch']} "
                            f"thr={r['throughput_sps']:.3g}sps "
                            f"P={r['p_total_w']:.2f}W "
                            f"E={r['e_total_j']:.3g}J"),
            })
        for claim, ok in table2_domains.validate(t2).items():
            rows.append({"name": f"table2_claim:{claim}",
                         "us_per_call": 0.0, "derived": str(ok)})

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},\"{r['derived']}\"")
    sys.stdout.flush()  # keep the CSV clean when stderr is merged via 2>&1
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
