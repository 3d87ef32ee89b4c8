"""Config 15: steady-state serving throughput through the program cache.

The serving-path claim (ISSUE 2): once a row bucket's AOT executable
exists, transform calls are compile-free and copy-minimal, so WARM
steady-state throughput must beat the COLD first call — which pays
trace + XLA compile + H2D — by a wide margin (acceptance: >= 3x on the
1M x 1024 PCA shape). Three numbers, one JSON line:

  - ``cold_s``: first-ever transform at this bucket (compile included).
  - ``value`` (rows/s): warm steady-state on a DEVICE-RESIDENT batch —
    the repeated-inference fast path.
  - ``host_stream_rows_s``: warm host-resident blocks through the
    double-buffered ``serve_stream`` path (H2D of block k+1 overlapped
    with compute of block k) — the Spark-executor serving posture, where
    batches arrive in host memory.

Shape overrides for small hosts: ``TPUML_BENCH_ROWS`` / ``_COLS`` /
``_K`` / ``_BLOCK``.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, time_amortized
from spark_rapids_ml_tpu.utils.envknobs import env_int

N = env_int("TPUML_BENCH_ROWS", 1_000_000)
D = env_int("TPUML_BENCH_COLS", 1024)
K = env_int("TPUML_BENCH_K", 16)
BLOCK = env_int("TPUML_BENCH_BLOCK", 131_072)


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.core import serving
    from spark_rapids_ml_tpu.models.pca import PCAModel

    x = jax.random.normal(jax.random.key(15), (N, D), dtype=jnp.float32)
    float(jnp.sum(x[0]))
    q, _ = np.linalg.qr(np.random.default_rng(0).normal(size=(D, K)))
    model = PCAModel("bench", q.astype(np.float32), np.full(K, 1.0 / K))

    serving.clear_program_cache()

    # COLD: the first call at this bucket pays trace + compile (+ the
    # model's one-time component upload).
    t0 = time.perf_counter()
    out = model.transform(x)
    float(out[0, 0])
    cold_s = time.perf_counter() - t0
    assert serving.program_cache_stats()["compiles"] >= 1

    # WARM device-resident steady state: same bucket, zero compiles.
    before = serving.program_cache_stats()["compiles"]
    warm_s = time_amortized(
        lambda: model.transform(x), lambda o: float(o[0, 0]), inner=5
    )
    assert serving.program_cache_stats()["compiles"] == before, "warm path compiled"

    # WARM host-streaming steady state: double-buffered block pipeline.
    n_blocks = max(1, N // BLOCK)
    host_blocks = [
        np.asarray(x[i * BLOCK : (i + 1) * BLOCK]) for i in range(n_blocks)
    ]
    rows_streamed = sum(b.shape[0] for b in host_blocks)

    def stream_once() -> None:
        for _ in model.transform(iter(host_blocks)):
            pass

    stream_once()  # warm the block bucket
    t0 = time.perf_counter()
    stream_once()
    stream_s = time.perf_counter() - t0

    shape = "1Mx1024_k16" if (N, D, K) == (1_000_000, 1024, 16) else f"{N}x{D}_k{K}"
    emit(
        f"serving_warm_pca_transform_{shape}",
        N / warm_s,
        "rows/s",
        wall_s=round(warm_s, 4),
        cold_s=round(cold_s, 4),
        warm_vs_cold=round((N / warm_s) / (N / cold_s), 1),
        host_stream_rows_s=round(rows_streamed / stream_s, 1),
        cache=serving.program_cache_stats(),
        **bytes_roofline(4.0 * (N * D + N * K), warm_s),
    )


if __name__ == "__main__":
    main()
