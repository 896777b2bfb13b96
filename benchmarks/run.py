"""Run every paper-table benchmark (one module per table/figure) and the
kernel microbench; print consolidated CSV. The roofline report reads the
dry-run artifacts separately: `python -m benchmarks.roofline`."""
from __future__ import annotations

import contextlib
import io
import time


def _run(name, main_fn):
    print(f"===== {name} =====", flush=True)
    t0 = time.time()
    try:
        main_fn()
        status = "ok"
    except Exception as e:  # noqa: BLE001
        import traceback
        traceback.print_exc()
        status = f"FAILED: {e}"
    print(f"----- {name}: {status} ({time.time()-t0:.1f}s)\n", flush=True)
    return status == "ok"


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_cifar_hybrid, bench_countsketch, bench_factored_grad,
        bench_kernels, bench_memory_complexity, bench_mnist,
        bench_monitoring, bench_pinn, bench_reconstruction_error,
    )
    results = {}
    results["kernels"] = _run("bench_kernels (kernel vs oracle)",
                              bench_kernels.main)
    results["countsketch"] = _run(
        "bench_countsketch (DP wire bytes + convergence gate)",
        bench_countsketch.main)
    results["factored"] = _run(
        "bench_factored_grad (beyond-paper low-rank grads)",
        bench_factored_grad.main)
    results["recon"] = _run(
        "bench_reconstruction_error (Thm 4.2/4.3)",
        bench_reconstruction_error.main)
    results["memory"] = _run(
        "bench_memory_complexity (paper §4.7 table)",
        bench_memory_complexity.main)
    results["mnist"] = _run("bench_mnist (paper Fig. 1)",
                            bench_mnist.main)
    results["cifar"] = _run("bench_cifar_hybrid (paper Fig. 2)",
                            bench_cifar_hybrid.main)
    results["pinn"] = _run("bench_pinn (paper Figs. 3/4)",
                           bench_pinn.main)
    results["monitoring"] = _run("bench_monitoring (paper Fig. 5)",
                                 bench_monitoring.main)
    print("===== summary =====")
    for k, ok in results.items():
        print(f"{k}: {'ok' if ok else 'FAILED'}")
    if not all(results.values()):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
