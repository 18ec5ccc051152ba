"""Paper-table reproductions and the roofline table.

Prints ``name,us_per_call,derived`` CSV: the rows of the dry-run roofline
table (``us_per_call`` is its analytic bound), then Table 1 (NAS
objectives) and Table 2 (target domains) with their claim checks
(``us_per_call`` 0).  Nothing here is timed: device speed is measured by
``benchmarks/chip/`` and recorded in ``PERF_LEDGER.jsonl``.
Full-size runs: ``python -m benchmarks.run --full``; default sizes finish
on the CPU box in a few minutes.
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale NAS settings (hours)")
    ap.add_argument("--skip-nas", action="store_true",
                    help="only the roofline table")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []

    from benchmarks import roofline_table
    rows += roofline_table.run(log=lambda *a: print(*a, file=sys.stderr))
    roofline_table.write_markdown(log=lambda *a: print(*a, file=sys.stderr))

    if not args.skip_nas:
        from benchmarks import table1_objectives, table2_domains
        gens = 12 if args.full else 3
        samples = 1600 if args.full else 240
        steps = 300 if args.full else 60

        t1 = table1_objectives.run(generations=gens, samples=samples,
                                   train_steps=steps,
                                   log=lambda *a: print(*a, file=sys.stderr))
        for r in t1:
            rows.append({
                "name": f"table1:{r['nas_objective']}:{r['impl_strategy']}",
                "us_per_call": 0.0,
                "derived": (f"thr={r['throughput_sps']:.3g}sps "
                            f"P={r['p_total_w']:.2f}W "
                            f"E={r['e_total_uj']:.3g}uJ "
                            f"params={r['params']}"),
            })
        for claim, ok in table1_objectives.validate(t1).items():
            rows.append({"name": f"table1_claim:{claim}",
                         "us_per_call": 0.0, "derived": str(ok)})

        t2 = table2_domains.run(generations=gens, samples=samples,
                                train_steps=steps,
                                log=lambda *a: print(*a, file=sys.stderr))
        for r in t2:
            rows.append({
                "name": f"table2:{r['device'].split(' (')[0]}",
                "us_per_call": 0.0,
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


if __name__ == "__main__":
    main()
