# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import importlib
import sys
import time

MODULES = [
    "tab1_word2vec_serving",
    "tab2_text_classification",
    "tab3_extreme_classification",
    "tab4_heterogeneous",
    "tab5_index_comparison",
    "tab6_lsh_threshold",
    "tab7_page_packing",
    "tab8_model_updates",
    "tab9_compression",
    "fig8_latency_curves",
    "fig13_validation_overheads",
    "fig14_cache_policies",
    "bench_serving_backends",
    "bench_faults",
    "bench_traffic",
    "bench_recovery",
    "roofline_table",
]


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    only = sys.argv[1:] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = []
    for name in MODULES:
        if only and not any(o in name for o in only):
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:              # keep the harness running
            failures.append((name, repr(e)))
            print(f"{name}/ERROR,0.0,{type(e).__name__}")
            continue
        for r, us, derived in rows:
            print(f"{r},{us:.1f},{derived}")
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
