"""Benchmark driver: one benchmark per paper table/figure plus kernel
microbenchmarks and the dry-run roofline report.

Prints ``name,us_per_call,derived`` CSV rows (the contract of this repo)."""

from __future__ import annotations


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    from benchmarks import kernels_bench, paper_figs, roofline_report, tracelint_bench

    paper_figs.run_all()
    kernels_bench.run_all()
    roofline_report.run_all()
    tracelint_bench.run_all()


if __name__ == "__main__":
    main()
