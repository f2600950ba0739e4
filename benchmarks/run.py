"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (plus human summaries).

  PYTHONPATH=src python -m benchmarks.run [--only fig2 fig7 ...] [--fast]

Compiled programs are kept in JAX's persistent cache
(``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` or
``.jax_cache/`` at the root of the checkout).
"""
import argparse
import sys

from . import async_exec, figures, kernelzoo, online, serving, streaming


ALL = {
    "async": async_exec.async_exec,
    "fig2": figures.fig2_scaling_cores,
    "fig3": figures.fig3_scaling_data,
    "fig4": figures.fig4_parity,
    "fig5": figures.fig5_load_distribution,
    "fig7": figures.fig7_node_failure,
    "usps": figures.usps_reconstruction,
    "psi2": figures.psi2_variants,
    "lm": figures.lm_train_microbench,
    "stream": streaming.streaming_map,
    "regmap": streaming.reg_map_backends,
    "svi": streaming.svi_map,
    "predict": serving.predict_serving,
    "serve_ext": serving.serving_extensions,
    "frontend": serving.frontend_serving,
    "kernelzoo": kernelzoo.kernel_zoo,
    "online": online.online_updates,
}

FAST_ARGS = {
    "async": dict(n=16_384, m=16, chunk=512, iters=2, refresh_sweep=(1, 4),
                  staleness=16, straggler_rates=(0.0, 0.4),
                  straggler_factor=6.0, straggler_iters=4, n_strag=4_096),
    "fig2": dict(n=4000, iters=2),
    "fig3": dict(iters=2),
    "fig4": dict(n=200, iters=40),
    "fig5": dict(n=8000, iters=3),
    "fig7": dict(n=150, iters=40),
    "usps": dict(n_small=150, n_big=500, iters=50),
    "psi2": dict(n=2048, iters=2),
    "lm": dict(steps=3),
    "stream": dict(n_parity=4000, n_big=60_000, m=48, block=1024,
                   budget_gb=0.5, iters=2, host_n0=40_000,
                   host_mults=(1, 2, 4), host_chunk=1024, host_bpc=8),
    "regmap": dict(n=4096, m=32, block=1024, iters=2),
    "svi": dict(n=4096, m=32, block=256, iters=2, batch_sweep=(1, 2, 4, 8),
                n_mults=(1, 2)),
    "predict": dict(n=4096, m_sweep=(16, 32), t_sweep=(64, 256, 1024),
                    block=128, iters=2),
    "serve_ext": dict(n=4096, m=32, t=256, block=64, s_sweep=(1, 8, 32),
                      n_models_sweep=(1, 2, 4), iters=2),
    "frontend": dict(n=4096, m=32, block=32, t_req=4, duration_s=1.0,
                     overload=4.0, swap_every_ms=100.0),
    "kernelzoo": dict(n=4096, m=32, t=512, block=512, iters=2),
    "online": dict(m=16, k=8, n_sweep=(1_000, 4_000), k_sweep=(1, 8),
                   iters=2),
}


def main() -> None:
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="--only targets: " + " ".join(ALL))
    ap.add_argument("--only", nargs="*", default=None, choices=list(ALL),
                    metavar="TARGET",
                    help="benchmarks to run (default: all; see list below)")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = args.only or list(ALL)
    rows = []
    for name in names:
        print(f"== {name} ==")
        kwargs = FAST_ARGS.get(name, {}) if args.fast else {}
        try:
            rows.extend(ALL[name](**kwargs))
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            rows.append((f"{name}/FAILED", 0.0, repr(e)))
    print("\nname,us_per_call,derived")
    for r in rows:
        print(f"{r[0]},{r[1]:.3f},{r[2]}")
    if any("FAILED" in r[0] for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()
