"""Benchmark harness: one module per paper table. Prints
``name,us_per_call,derived`` CSV (plus the roofline table if the dry-run
sweep results exist)."""
import os


def main() -> None:
    from benchmarks import bench_emulation, bench_vector, bench_ocean
    print("# Table 1 — emulation overhead (paper §5)")
    bench_emulation.main()
    print("# Table 2 — vectorized throughput (paper §5)")
    bench_vector.main()
    print("# Table 2 — EnvPool vs synchronous on jittered host envs")
    from benchmarks import bench_pool_host
    bench_pool_host.main()
    print("# §4 — Ocean solve table")
    bench_ocean.main()
    print("# §3.3 — kernel backend autotune")
    from benchmarks import bench_kernels
    bench_kernels.main()
    if os.path.exists("results/dryrun_baseline_final.json"):
        print("# §Roofline (from dry-run sweep)")
        from benchmarks import roofline
        import json
        with open("results/dryrun_baseline_final.json") as f:
            results = json.load(f)
        for r in results:
            if r.get("status") == "ok":
                print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},"
                      f"{max(r['t_compute_s'], r['t_memory_s'], r['t_collective_s'])*1e6:.0f},"
                      f"bottleneck={r['bottleneck']};frac={r.get('roofline_fraction', 0):.4f}")


if __name__ == '__main__':
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
