"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract:
  * Table 1 / Fig 9 rows: us_per_call = benchmark total time, derived =
    ideal end-to-end Amdahl speedup (paper value appended for comparison);
  * Fig 8: hardware-vs-software ratio; Fig 2: frontier gaps; Fig 3:
    complexity crossovers; planner: per-arch bounded speedups.

Timings of the offload runtime come from the chip benchmark
(``benchmarks/chip/run.py``), not from here.
"""

from __future__ import annotations


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("section,name,us_per_call,derived")

    # --- Table 1 / Figure 9: the 27-benchmark Amdahl suite ------------------
    from benchmarks.amdahl_suite import PAPER_TABLE1, run_suite
    rows = run_suite()
    speedups = []
    for r in rows:
        paper_pct, paper_s = PAPER_TABLE1[r.name]
        speedups.append(r.end_to_end_speedup)
        print(f"table1,{r.name},{1e6 * r.total_time_s:.1f},"
              f"speedup={r.end_to_end_speedup:.2f}x|frac={100*r.fraction:.2f}%"
              f"|paper={paper_s:.2f}x|paper_frac={paper_pct:.2f}%")
    ss = sorted(speedups)
    median = ss[len(ss) // 2]
    mean = sum(ss) / len(ss)
    print(f"table1,MEDIAN,,{median:.2f}x (paper 1.94x)")
    print(f"table1,MEAN,,{mean:.2f}x (paper 9.39x)")

    # --- Figure 8: prototype data-movement split ------------------------------
    from benchmarks.conversion_bottleneck import run as fig8
    r8 = fig8()
    print(f"fig8,software_fft,{1e6 * r8['software_fft_s']:.1f},measured")
    print(f"fig8,hardware_total,{1e6 * r8['hardware_total_s']:.1f},"
          f"movement={r8['hardware_movement_pct']:.3f}% (paper "
          f"{r8['paper_movement_pct']}%)")
    print(f"fig8,slowdown,,{r8['hardware_vs_software']:.1f}x slower than "
          f"software (paper {r8['paper_hardware_vs_software']:.1f}x on rpi4)")
    print(f"fig8,sim_intensity_rel_err,,{r8['sim_intensity_rel_err']:.2e}")

    # --- Figure 2: converter Pareto frontier ------------------------------------
    from benchmarks.pareto import run as fig2
    r2 = fig2()
    for k in ("kim_dac_gap", "liu_adc_gap", "anderson_dac_gap",
              "anderson_adc_gap"):
        print(f"fig2,{k},,{r2[k]:.2f}x")

    # --- Figure 3: complexity crossover -------------------------------------------
    from benchmarks.complexity_fig import run as fig3
    r3 = fig3()
    for name, n in r3["crossover_1x"].items():
        n10 = r3["crossover_10x"][name]
        print(f"fig3,{name.replace(' ', '_')},,"
              f"crossover_1x=N{n}|crossover_10x=N{n10}")

    # --- Planner: the 10 assigned archs under the decision rule --------------------
    from benchmarks.planner_table import run as planner
    for row in planner():
        mm = row["flops_pct"].get("matmul", 0.0)
        print(f"planner,{row['arch']},,mvm={row['mvm_speedup']:.2f}x"
              f"|fourier={row['fourier_speedup']:.2f}x"
              f"|matmul_flops={mm:.1f}%"
              f"|worthwhile={row['mvm_worthwhile']}"
              f"|conversion_bound={row['mvm_conversion_bound']}")


if __name__ == "__main__":
    main()
