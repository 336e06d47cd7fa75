# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Every suite runs in this one process (a second JAX process would fight
# this one for the accelerator).  A failing suite is reported as an
# ``__ERROR`` row, the remaining suites still run, and the exit code is
# non-zero.
import sys
import time


def main() -> int:
    from benchmarks import (
        compress_bench,
        fig1_sparsity,
        fig6_utilization,
        fig7_comparison,
        kernel_bench,
        roofline,
        table2_configs,
    )
    suites = [
        ("fig1_sparsity", fig1_sparsity),
        ("table2_configs", table2_configs),
        ("fig6_utilization", fig6_utilization),
        ("fig7_comparison", fig7_comparison),
        ("kernel_bench", kernel_bench),
        ("compress_bench", compress_bench),
        ("roofline", roofline),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, mod in suites:
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            failed.append(name)
            rows = [f"{name}__ERROR,0,{type(e).__name__}:{e}"]
        for r in rows:
            print(r)
        dt = (time.perf_counter() - t0) * 1e6
        print(f"{name}__suite,{dt:.0f},done")
    if failed:
        print(f"failed suites: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
