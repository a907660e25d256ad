"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. The rows are the paper's
analytical tables and the deterministic count gates; ``us_per_call`` is a
host CPU timing, not a speed of the system (that comes from
``python3 -m bench.run`` on the chip). Fast by default; ``--full`` adds the
slower quantization sweep over more datasets.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, help="substring filter on benchmark name")
    args = ap.parse_args(argv)

    from benchmarks.autotune_bench import autotune_rows
    from benchmarks.comm_bench import comm_rows
    from benchmarks.delta_bench import delta_rows
    from benchmarks.obs_bench import obs_rows
    from benchmarks.relocal_bench import relocal_rows
    from benchmarks.fig07_quant import fig07_quant_accuracy
    from benchmarks.kernel_bench import bench_kernels_rows, kernel_rows, spmm_compare_rows
    from benchmarks.paper_figs import (
        comm_tier_rows,
        fig01_baseline_comm,
        fig09_mesh_sweep,
        fig10_11_energy_vs_baseline,
        fig12_cmesh,
        fig13_edp,
        halo_vs_broadcast,
        tbl3_comm_fraction,
    )
    from benchmarks.paper_tables import (
        tbl_accel_compare,
        tbl_chips,
        tbl_dataflow,
        tbl_optimal_k,
    )

    suites = [
        ("fig01", fig01_baseline_comm),
        ("optk", tbl_optimal_k),
        ("dataflow", tbl_dataflow),
        ("fig09", fig09_mesh_sweep),
        ("fig10/11", fig10_11_energy_vs_baseline),
        ("fig12", fig12_cmesh),
        ("fig13", fig13_edp),
        ("tbl3", tbl3_comm_fraction),
        ("halo", halo_vs_broadcast),
        ("comm-tier", comm_tier_rows),
        ("comm", comm_rows),
        ("delta", delta_rows),
        ("relocal", relocal_rows),
        ("autotune", autotune_rows),
        ("chips", tbl_chips),
        ("tbl4/6/7", tbl_accel_compare),
        ("kernels", kernel_rows),
        ("kernels-ragged", bench_kernels_rows),
        ("spmm", lambda: spmm_compare_rows(full=args.full)),
        ("obs", obs_rows),
        ("fig07", lambda: fig07_quant_accuracy(
            datasets=("cora", "citeseer", "pubmed") if args.full else ("cora",),
            epochs=120,
        )),
    ]

    print("name,us_per_call,derived")
    failures = 0
    for label, fn in suites:
        if args.only and args.only not in label:
            continue
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except FileNotFoundError as e:
            print(f"{label},0.0,SKIPPED({e})")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{label},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
