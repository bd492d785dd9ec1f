"""Benchmark driver — one section per paper table/figure.

Prints ``name,value,derived`` CSV lines (benchmark contract).  Sections:
  table1  — paper Table I (analytic FPGA model vs published)
  cycles  — paper eq. 6 schedules + latency/energy vs SIP
  mnist   — paper Figs. 8/9 (negative-activation + cycle-saving per class)
  kernel  — TPU digit-plane kernel (plane skipping, runtime precision)
"""

from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import bench_cycles, bench_kernel, bench_mnist_stats, bench_table1
    enable_compile_cache()
    sections = [
        ("table1", bench_table1.run),
        ("cycles", bench_cycles.run),
        ("kernel", bench_kernel.run),
        ("mnist", bench_mnist_stats.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,value,derived")
    for name, fn in sections:
        if only and name != only:
            continue
        t0 = time.time()
        for row in fn():
            print(row, flush=True)
        print(f"_section.{name}_seconds,{time.time() - t0:.1f},", flush=True)


if __name__ == "__main__":
    main()
