"""Benchmark entry point: one section per paper table/figure + kernels +
the dry-run roofline summary. Prints ``name,us_per_call,derived`` CSV rows
plus validation lines against the paper's reported numbers.

    PYTHONPATH=src python -m benchmarks.run [--skip-roofline]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()

    from . import (admission_overlap, decode_prefetch, fig2_patterns,
                   fig5_throughput, fig6_hitrate, host_compute,
                   kernels_micro, table1_compute_comm, table5_energy)
    sections = [table1_compute_comm, fig2_patterns, fig5_throughput,
                fig6_hitrate, table5_energy, kernels_micro, decode_prefetch,
                host_compute, admission_overlap]
    if not args.skip_roofline:
        from . import roofline
        sections.append(roofline)

    failures = 0
    for mod in sections:
        print(f"\n########## {mod.__name__} ##########")
        try:
            mod.main()
        except Exception:  # noqa: BLE001 — report all sections
            failures += 1
            traceback.print_exc()
    if failures:
        print(f"\n{failures} benchmark section(s) failed", file=sys.stderr)
        sys.exit(1)
    print("\nall benchmark sections completed")


if __name__ == "__main__":
    main()
