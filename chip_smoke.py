#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path runs on the chip.

Drives the estimator and serving main path ONCE through the public entry
points, on the attached TPU, at this repo's own headline sizes, and checks
every result against a reference. One process, no children; data is made
on the device from ``--seed``. It changes no behaviour of the library and
claims no speed: the seconds it prints are set-up information.

    python chip_smoke.py              # one chip: device, pca_fit, host_fit,
                                      #   kernels, serving
    python chip_smoke.py --chips 4    # four chips: ONLY the mesh PCA fit and
                                      #   what it is compared with

Output: one JSON line per phase (name, shapes, cold and warm seconds, max
error against the reference with its tolerance, peak ``bytes_in_use``),
then as the LAST line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits non-zero with ``"ok": false`` when ``jax.devices()[0].platform``
is not ``"tpu"``, when any phase raises, or when a comparison misses its
tolerance. No phase's failure is caught and passed over: the first one
ends the run.

``--rehearse`` is the CPU rehearsal of the control flow (tiny sizes, Pallas
kernels in interpret mode, virtual devices for ``--chips 4``). A rehearsal
is never a pass: it ends ``"ok": false`` and exits 1 even when every phase
went through (2 when one did not).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
import warnings
from contextlib import contextmanager
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

K_PCA = 16


class SmokeFailure(AssertionError):
    """A phase's result missed its reference, tolerance or expected route."""


# sizes: this repo's headline shapes; the rehearsal's are control-flow only.
FULL = dict(
    pca_rows=1_000_000, pca_cols=1024, transform_rows=100_000,
    host_rows=262_144, f64_rows=65_536, f64_cols=256,
    km_rows=2_000_000, km_cols=16, km_iters=10,
    umap_rows=50_000, umap_cols=64, umap_epochs=30,
    split_values=1_000_000, update_rows=65_536, update_cols=3000, update_k=1000,
    mesh_rows_per_chip=1_000_000,
)
TINY = dict(
    pca_rows=4096, pca_cols=128, transform_rows=512,
    host_rows=2048, f64_rows=8192, f64_cols=32,
    km_rows=20_000, km_cols=16, km_iters=3,
    umap_rows=600, umap_cols=16, umap_epochs=5,
    split_values=4096, update_rows=2048, update_cols=128, update_k=16,
    mesh_rows_per_chip=2048,
)
REQUEST_ROWS = (1, 7, 64, 1000)
# Every pow-2 bucket a coalesced micro-batch (<= max_batch 256 rows) or a
# lone 1000-row request can land in.
WARM_BUCKETS = (8, 16, 32, 64, 128, 256, 1024)


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def check(what: str, err: float, tol: float) -> float:
    err = float(err)
    if not err <= tol:  # also catches NaN
        raise SmokeFailure(f"{what}: error {err:.3e} exceeds tolerance {tol:.1e}")
    return err


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, round(time.perf_counter() - t0, 3)


def memory(device=None) -> dict:
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def every_device_memory() -> list:
    import jax

    return [{"device": dev.id, **memory(dev)} for dev in jax.devices()]


def device_record() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


@contextmanager
def spy(owner, name: str, seen: list, note):
    """Record how the library calls ``owner.name``: each call appends
    ``note(args, kwargs, result)`` to ``seen``. No argument and no result
    is changed; the real function is yielded and restored on exit."""
    real = getattr(owner, name)

    def wrapper(*args, **kwargs):
        out = real(*args, **kwargs)
        seen.append(note(args, kwargs, out))
        return out

    setattr(owner, name, wrapper)
    try:
        yield real
    finally:
        setattr(owner, name, real)


def expect_route(what: str, seen: list, want: dict) -> dict:
    if not seen or any(call != want for call in seen):
        raise SmokeFailure(f"{what} took route {seen}, want {want}")
    return seen[-1]


def native_state() -> dict:
    """Whether anything on the run's path loaded the optional C++ host
    library (nothing needs it; asking must not build it — that would be a
    child process)."""
    try:
        from spark_rapids_ml_tpu import native
    except ImportError:
        return {"native_library_loaded": None}
    return {"native_library_loaded": native.loaded()}


# --- data and the plain reference ---------------------------------------


def make_pca_rows(key, n: int, d: int):
    """(n, d) f32 rows with a planted spectrum: 16 leading variances from
    16 down to 4 (adjacent gaps ~9%, a factor 4 over the unit bulk),
    mixed by a random Householder reflection (dense eigenvectors, cheap to
    compile) and shifted by a non-zero column mean — so the leading
    eigenvectors are well conditioned and centring matters."""
    import jax
    import jax.numpy as jnp

    kz, kv, km = jax.random.split(key, 3)
    top = 16.0 * (4.0 / 16.0) ** (jnp.arange(K_PCA) / (K_PCA - 1.0))
    scale = jnp.sqrt(jnp.concatenate([top, jnp.ones((d - K_PCA,))]))
    v = jax.random.normal(kv, (d,), dtype=jnp.float32)
    v = v / jnp.linalg.norm(v)
    z = jax.random.normal(kz, (n, d), dtype=jnp.float32) * scale
    mean = 3.0 * jax.random.normal(km, (d,), dtype=jnp.float32)
    return z - 2.0 * (z @ v)[:, None] * v + mean


def generate(fn, key, *shape, out_shardings=None):
    """Run a data generator jitted on the device (shape is static)."""
    import jax

    static = tuple(range(1, 1 + len(shape)))
    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.block_until_ready(jax.jit(fn, static_argnums=static, **kw)(key, *shape))


def reference_cov(x):
    """Plain jax.numpy: column mean, centred Gram at "highest"."""
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=0)
    xc = x - mean
    with jax.default_matmul_precision("highest"):
        return (xc.T @ xc) / (x.shape[0] - 1)


def reference_eig(cov, k: int):
    """(pc (d, k), explained-variance ratio (k,)) by jnp.linalg.eigh."""
    import jax.numpy as jnp

    w, v = jnp.linalg.eigh(cov)
    w = jnp.maximum(w[::-1], 0)
    return v[:, ::-1][:, :k], w[:k] / jnp.sum(w)


def pca_errors(model, pc_ref, ev_ref):
    """(max |explainedVariance - ref|, max |sign-aligned pc - ref|)."""
    import numpy as np

    pc = np.asarray(model.pc, dtype=np.float64)
    ev = np.asarray(model.explainedVariance, dtype=np.float64)
    pc_ref = np.asarray(pc_ref, dtype=np.float64)
    ev_ref = np.asarray(ev_ref, dtype=np.float64)
    if pc.shape != pc_ref.shape or ev.shape != ev_ref.shape:
        raise SmokeFailure(f"pca shapes {pc.shape}/{ev.shape} != reference")
    sign = np.sign(np.sum(pc * pc_ref, axis=0))
    return (
        float(np.max(np.abs(ev - ev_ref))),
        float(np.max(np.abs(pc * sign - pc_ref))),
    )


def make_blobs(key, n: int, d: int, blobs: int):
    import jax
    import jax.numpy as jnp

    kc, kl, kn = jax.random.split(key, 3)
    centers = 6.0 * jax.random.normal(kc, (blobs, d), dtype=jnp.float32)
    labels = jax.random.randint(kl, (n,), 0, blobs)
    return centers[labels] + jax.random.normal(kn, (n, d), dtype=jnp.float32)


# --- phases --------------------------------------------------------------


def phase_device(rehearse: bool) -> None:
    import jax

    from spark_rapids_ml_tpu.core import membudget
    from spark_rapids_ml_tpu.core.serving import configure_compile_cache

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    free = membudget.free_hbm_bytes()
    cache_dir = configure_compile_cache()
    emit(
        phase="device", **device_record(),
        default_backend=jax.default_backend(),
        bytes_limit=stats.get("bytes_limit"),
        free_hbm_bytes=free,
        compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        # 0 entries = every "cold" time below includes the compile itself.
        compile_cache_entries_at_start=(
            len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0
        ),
        jax=jax.__version__,
    )
    if rehearse:
        return
    if jax.default_backend() != "tpu":
        raise SmokeFailure(f"default backend is {jax.default_backend()!r}")
    if free is None:
        # None resolves the fit admission gate to "off" — on a chip that
        # means the gate (and its streaming detour) is silently dead.
        raise SmokeFailure("membudget.free_hbm_bytes() is None on a TPU")


def _fit_pca_device(x):
    from spark_rapids_ml_tpu.feature import PCA

    model = PCA().setK(K_PCA).fit(x)
    return model, (model._pc_raw, model._ev_raw)


def run_pca_fit(key, sz, eig):
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, d, m = sz["pca_rows"], sz["pca_cols"], sz["transform_rows"]
    x = generate(make_pca_rows, key, n, d)
    (model, _), cold = timed(lambda: _fit_pca_device(x))
    (model, _), warm = timed(lambda: _fit_pca_device(x))
    pc_ref, ev_ref = eig(jax.jit(reference_cov)(x))
    ev_err, pc_err = pca_errors(model, pc_ref, ev_ref)
    # Tolerances: both sides accumulate the same 1M-row f32 Gram at
    # "highest" (~1e-6 relative), and the planted gaps are >= 9% of the
    # eigenvalue, so vectors move by ~1e-6/0.09 ~ 1e-5; explained-variance
    # ratios are O(1e-2) numbers with the same relative error. 1e-5 / 1e-3
    # leave a decade or more for the eigensolvers' own differences (the
    # library's self-selecting subspace iteration vs jnp.linalg.eigh).
    check("pca_fit explainedVariance", ev_err, 1e-5)
    check("pca_fit pc (sign-aligned)", pc_err, 1e-3)

    head = x[:m]
    out, t_cold = timed(lambda: model.transform(head))
    out, t_warm = timed(lambda: model.transform(head))
    if out.shape != (m, K_PCA):
        raise SmokeFailure(f"transform shape {out.shape} != {(m, K_PCA)}")
    with jax.default_matmul_precision("highest"):
        want = head @ jnp.asarray(model._pc_raw, dtype=head.dtype)
    scale = float(jnp.max(jnp.abs(want)))
    # The projection serves at the serving-family precision ("highest" by
    # default): f32-grade agreement relative to the output's magnitude.
    t_err = check(
        "pca transform", float(jnp.max(jnp.abs(out - want))) / scale, 1e-5
    )
    emit(
        phase="pca_fit", rows=n, cols=d, k=K_PCA, cold_s=cold, warm_s=warm,
        explained_variance_err=ev_err, pc_err=pc_err,
        tol={"explained_variance": 1e-5, "pc": 1e-3, "transform_rel": 1e-5},
        transform_rows=m, transform_cold_s=t_cold, transform_warm_s=t_warm,
        transform_rel_err=t_err, **memory(),
    )
    return model


def run_host_fit(key, sz, eig, rehearse: bool):
    """The path a user's numpy rows take: host f32 rows through the live
    admission gate (admitted in memory, not streamed or degraded), then a
    float64 host fit on the ``auto -> dd`` route."""
    import jax
    import numpy as np

    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix
    from spark_rapids_ml_tpu.utils import tracing

    def admission():
        return {
            name: tracing.counter_value(name)
            for name in (
                "fit.admission.admitted", "fit.admission.degraded",
                "fit.admission.rejected", "fit.oom.events",
            )
        }

    def expect_admitted(before, fits: int, what: str):
        now = admission()
        delta = {k: now[k] - before[k] for k in now}
        want = {
            "fit.admission.admitted": fits, "fit.admission.degraded": 0,
            "fit.admission.rejected": 0, "fit.oom.events": 0,
        }
        if delta != want:
            raise SmokeFailure(f"{what}: admission counters moved {delta}, want {want}")
        return delta

    k1, k2 = jax.random.split(key)
    n, d = sz["host_rows"], sz["pca_cols"]
    x_dev = generate(make_pca_rows, k1, n, d)
    x_host = np.asarray(x_dev)  # a user's numpy rows
    pc_ref, ev_ref = eig(jax.jit(reference_cov)(x_dev))
    del x_dev

    before = admission()
    t0 = time.perf_counter()
    model = PCA().setK(K_PCA).fit(x_host)
    ev_err, pc_err = pca_errors(model, pc_ref, ev_ref)  # reads = sync
    cold = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    np.asarray(PCA().setK(K_PCA).fit(x_host).explainedVariance)
    warm = round(time.perf_counter() - t0, 3)
    # KMeans takes host rows through core/ingest.py::prepare_rows.
    km = KMeans().setK(K_PCA).setMaxIter(3).setSeed(1).fit(x_host)
    if not np.all(np.isfinite(km.clusterCenters())):
        raise SmokeFailure("host KMeans returned non-finite centres")
    delta = expect_admitted(before, 3, "host f32 fits")
    # Same reasoning as pca_fit (same spectrum, 262k rows).
    check("host_fit explainedVariance", ev_err, 1e-5)
    check("host_fit pc (sign-aligned)", pc_err, 1e-3)

    # float64 rows, x64 off: precision "auto" must take the double-double
    # route (ops/linalg.py::resolve_precision), seen here by a spy rather
    # than inferred from the error. Column means ~1e3 over unit spread; the
    # emulation's covariance error (~2e-7 absolute against the fp64
    # oracle) over planted gaps >= 0.36 moves a vector ~1e-6.
    n64, d64 = sz["f64_rows"], sz["f64_cols"]
    x64 = np.asarray(generate(make_pca_rows, k2, n64, d64), dtype=np.float64)
    x64 += 1e3 * (1.0 + np.arange(d64) / d64)
    cov = np.cov(x64, rowvar=False)
    w, v = np.linalg.eigh(cov)
    w, v = w[::-1], v[:, ::-1]
    est = PCA().setK(K_PCA)
    if rehearse:
        est = est.setPrecision("dd")  # off-TPU "auto" keeps f32
    dd_calls = []
    before64 = admission()
    with spy(RowMatrix, "_covariance_dd", dd_calls, lambda a, kw, out: "dd"):
        t0 = time.perf_counter()
        m64 = est.fit(x64)
        ev64, pc64 = pca_errors(m64, v[:, :K_PCA], w[:K_PCA] / w.sum())
        cold64 = round(time.perf_counter() - t0, 3)
    if not dd_calls:
        raise SmokeFailure("the float64 host fit did not take the dd route")
    expect_admitted(before64, 1, "host f64 fit")
    # The suite's oracle tolerance (absTol 1e-5, PCASuite.scala:71).
    check("host_fit f64 explainedVariance", ev64, 1e-5)
    check("host_fit f64 pc (sign-aligned)", pc64, 1e-5)
    emit(
        phase="host_fit", rows=n, cols=d, k=K_PCA, cold_s=cold, warm_s=warm,
        explained_variance_err=ev_err, pc_err=pc_err,
        f64_rows=n64, f64_cols=d64, f64_cold_s=cold64,
        f64_explained_variance_err=ev64, f64_pc_err=pc64, f64_route="dd",
        tol={"explained_variance": 1e-5, "pc": 1e-3, "f64": 1e-5},
        admission=delta, **memory(),
    )


def run_kernels(key, sz, rehearse: bool):
    """Every Pallas kernel that is on by default on a TPU, reached through
    its estimator and compiled, never interpreted (only the CPU rehearsal
    may interpret one). The opt-in PCA covariance kernel shares their
    off-CPU-only ``interpret`` branch; it passed the same check on the chip
    once (PR 21, CHANGES.md) and is left out to keep a cold run short."""
    import jax

    kx, ku = jax.random.split(key)
    km_model = kernels_kmeans(kx, sz, rehearse)
    kernels_kmeans_update(jax.random.fold_in(key, 31), sz)
    kernels_umap(ku, sz, rehearse)
    return km_model


def kernels_kmeans(key, sz, rehearse: bool):
    """KMeans().setK(100) (fused) and .setK(16) (lane-packed) at 2M x 16,
    against setBackend("xla") from the same init."""
    import jax.numpy as jnp
    import numpy as np

    import spark_rapids_ml_tpu.ops.pallas.kmeans as pk
    from spark_rapids_ml_tpu.clustering import KMeans

    n, d, iters = sz["km_rows"], sz["km_cols"], sz["km_iters"]
    x = generate(make_blobs, key, n, d, 64)
    spread = float(jnp.max(jnp.abs(x)))

    def fit(k, backend):
        est = KMeans().setK(k).setSeed(3).setMaxIter(iters).setTol(0.0)
        if backend is not None:
            est = est.setBackend(backend)
        model = est.fit(x)
        return model, model._centers_raw

    # The default backend ("auto") on the chip; the CPU rehearsal has to ask
    # for the kernel, which auto keeps off a CPU.
    backend = "fused" if rehearse else None
    fitted = {}
    for k, packed in ((100, False), (16, True)):
        seen = []
        note = lambda a, kw, out: {"packed": kw["packed"], "interpret": kw["interpret"]}
        with spy(pk, "lloyd_fused", seen, note):
            (model, _), cold = timed(lambda: fit(k, backend))
            (model, _), warm = timed(lambda: fit(k, backend))
        route = expect_route(
            f"KMeans k={k}", seen, {"packed": packed, "interpret": rehearse}
        )
        (xla, _), xla_s = timed(lambda: fit(k, "xla"))
        if int(model.numIter) != int(xla.numIter):
            raise SmokeFailure(
                f"KMeans k={k}: {model.numIter} iterations vs xla {xla.numIter}"
            )
        # Same init, same iteration count. The back ends score rows with
        # different reductions, so near-tie rows flip between them and the
        # flips compound over the passes: on the chip the centres differ by
        # 2.1e-4 of the data's spread at k=100 (more clusters than blobs, so
        # many boundary rows) and 4.4e-6 at k=16 (PR 21's runs).
        # trainingCost is sum(x^2) + sum(min score), two ~1e8 f32 sums that
        # cancel to ~3e7, so reduction order alone moves it (1e-6 relative
        # on the chip; tests/test_kmeans_fused.py saw 2e-4 between the back
        # ends on a CPU at 1,100 rows). Bounds: 1e-3 for both.
        c_err = check(
            f"KMeans k={k} centres vs xla",
            np.max(np.abs(model.clusterCenters() - xla.clusterCenters())) / spread,
            1e-3,
        )
        cost_err = check(
            f"KMeans k={k} trainingCost vs xla",
            abs(model.trainingCost - xla.trainingCost) / xla.trainingCost,
            1e-3,
        )
        fitted[k] = model
        emit(
            phase="kernels", kernel="kmeans." + ("packed" if packed else "fused"),
            rows=n, cols=d, k=k, iterations=int(model.numIter),
            cold_s=cold, warm_s=warm, xla_s=xla_s,
            centres_rel_err=c_err, training_cost_rel_err=cost_err,
            tol={"centres_rel": 1e-3, "training_cost_rel": 1e-3},
            route=route, **memory(),
        )
    return fitted[100]


def kernels_kmeans_update(key, sz) -> None:
    """The wide KMeans centre update (ops/kmeans.py, float32 rows at
    ``highest``): three bf16 passes on an exact three-piece split of the
    rows. Only the chip can show that its compiler keeps the split's
    roundings (it drops an ``astype`` round trip: PERF.md 7.9); a CPU
    passes this whatever the code does. (1) The pieces leave their program
    AS bfloat16 arrays, so nothing can carry excess precision, and are
    added up on the host: ``(hi + mid) + lo == x`` bit for bit. (2) The
    three-pass sums of one block against the ``HIGHEST`` one-hot matmul
    they replace, and both against float64 sums made on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.ops.kmeans import _assign_and_accumulate, _onehot_sums_split3
    from spark_rapids_ml_tpu.ops.precision import make_dot, split3_bf16
    from spark_rapids_ml_tpu.utils.tracing import counter_value

    kv, ke, kx, kl, kw, km = jax.random.split(key, 6)
    m = sz["split_values"]
    # Exponents over 2^-80 .. 2^80, both signs, some zeros.
    values = jax.random.normal(kv, (m,), jnp.float32) * jnp.exp2(
        jax.random.uniform(ke, (m,), jnp.float32, -80.0, 80.0)
    )
    values = values.at[::1000].set(0.0)
    (hi, mid, lo), split_s = timed(lambda: jax.jit(split3_bf16)(values))
    if not all(p.dtype == jnp.bfloat16 for p in (hi, mid, lo)):
        raise SmokeFailure(f"split3_bf16 pieces are {hi.dtype}, {mid.dtype}, {lo.dtype}")
    f32 = lambda p: np.asarray(p).astype(np.float32)
    back = (f32(hi) + f32(mid)) + f32(lo)
    want = np.asarray(values)
    wrong = int(np.sum(back.view(np.uint32) != want.view(np.uint32)))
    check("split3_bf16 (hi + mid) + lo == x, values off", wrong, 0)

    n, d, k = sz["update_rows"], sz["update_cols"], sz["update_k"]
    x = generate(make_blobs, kx, n, d, 3)
    labels = jax.random.randint(kl, (n,), 0, k)
    # Fractional weights and some masked rows: weightCol's path.
    mb = jnp.where(jax.random.uniform(km, (n,)) < 0.02, 0.0,
                   jax.random.uniform(kw, (n,), jnp.float32, 0.5, 2.0))

    def old(labels, mb, x):
        one_hot = jax.nn.one_hot(labels, k, dtype=x.dtype) * mb[:, None]
        return make_dot("highest")(one_hot.T, x), jnp.sum(one_hot, axis=0)

    (got, got_n), cold = timed(lambda: jax.jit(_onehot_sums_split3, static_argnums=1)(labels, k, mb, x))
    (ref, ref_n), ref_s = timed(lambda: jax.jit(old)(labels, mb, x))
    exact = np.zeros((k, d))
    np.add.at(exact, np.asarray(labels), np.asarray(x, np.float64) * np.asarray(mb, np.float64)[:, None])
    top = float(np.max(np.abs(exact)))
    # The chip's HIGHEST product is itself 9.0e-7 off the float64 sums
    # (PR 31's run; PERF.md 7.9 reads 6.1e-7 on a plain product), the three
    # passes 8.6e-8: so the float64 sums hold the new update, and its
    # distance from the old one is held loosely.
    err_64 = check("update: three-pass sums vs float64 host sums",
                   np.max(np.abs(np.asarray(got) - exact)) / top, 3e-7)
    err_old = check("update: three-pass sums vs HIGHEST one-hot matmul",
                    np.max(np.abs(np.asarray(got) - np.asarray(ref))) / top, 3e-6)
    ref_64 = float(np.max(np.abs(np.asarray(ref) - exact)) / top)
    n_err = check("update: counts vs HIGHEST",
                  np.max(np.abs(np.asarray(got_n) - np.asarray(ref_n))) / float(np.max(ref_n)), 1e-6)
    # Which update the Lloyd step itself traces for these rows.
    before = counter_value("kmeans.update.split3"), counter_value("kmeans.update.matmul")
    jax.block_until_ready(jax.jit(_assign_and_accumulate, static_argnums=(4, 5))(
        x, mb, jnp.sum(x * x, axis=1), x[:k], k, make_dot("highest")))
    traced = (counter_value("kmeans.update.split3") - before[0],
              counter_value("kmeans.update.matmul") - before[1])
    if traced != (1, 0):
        raise SmokeFailure(f"Lloyd step traced (split3, matmul) = {traced}, want (1, 0)")
    emit(
        phase="kernels", kernel="kmeans.update_split3",
        split_values=m, split_values_off=wrong, split_s=split_s,
        rows=n, cols=d, k=k, cold_s=cold, highest_s=ref_s,
        sums_vs_highest=err_old, sums_vs_float64=err_64, highest_vs_float64=ref_64,
        counts_vs_highest=n_err,
        tol={"sums_vs_float64": 3e-7, "sums_vs_highest": 3e-6, "counts": 1e-6, "split_values_off": 0},
        traced={"split3": traced[0], "matmul": traced[1]}, **memory(),
    )


def kernels_umap(key, sz, rehearse: bool) -> None:
    """UMAP at 50k x 64 -> 2-D: the tail scatter-add kernel inside the
    epoch program, then the kernel against the XLA scatter it replaces."""
    import jax.numpy as jnp
    import numpy as np

    import spark_rapids_ml_tpu.ops.pallas.umap as pu
    from spark_rapids_ml_tpu.manifold import UMAP

    n, d, epochs = sz["umap_rows"], sz["umap_cols"], sz["umap_epochs"]
    x = generate(make_blobs, key, n, d, 10)
    if rehearse:
        os.environ["TPUML_UMAP_SCATTER"] = "pallas"  # auto keeps XLA off-TPU

    def fit():
        est = UMAP().setNNeighbors(15).setNComponents(2).setNEpochs(epochs).setSeed(5)
        model = est.fit(x)
        return model, model._emb_raw

    plans, tails = [], []
    with spy(pu, "build_tail_plan", plans, lambda a, kw, out: (np.asarray(a[0]), out)), \
            spy(pu, "tail_accumulate", tails,
                lambda a, kw, out: {"interpret": kw["interpret"]}) as real_tail:
        (model, emb), cold = timed(fit)
        (model, emb), warm = timed(fit)
    route = expect_route("UMAP tail kernel", tails, {"interpret": rehearse})
    emb = jnp.asarray(emb)
    if emb.shape != (n, 2) or not bool(jnp.all(jnp.isfinite(emb))):
        raise SmokeFailure(f"UMAP embedding {emb.shape} not finite")
    # One epoch's attractive gradients over the fitted graph's edges,
    # through the kernel and through the XLA scatter.
    idx, (plan, cfg) = plans[-1]
    dst = jnp.asarray(idx)
    a, b = float(model.a), float(model.b)
    diff = emb[:, None, :] - emb[dst]
    d2 = jnp.sum(diff * diff, axis=2)
    att = (-2.0 * a * b * jnp.power(jnp.maximum(d2, 1e-12), b - 1.0)) / (
        1.0 + a * jnp.power(d2, b)
    )
    g = jnp.clip(att[:, :, None] * diff, -4.0, 4.0).reshape(-1, 2)
    got = real_tail(g, plan, cfg, interpret=rehearse)
    want = jnp.zeros((n, 2), jnp.float32).at[dst.reshape(-1)].add(g)
    # Same f32 sums in another order, relative to the largest row sum
    # (4.3e-7 on the chip; 2.5e-4 while the kernel's dot took one bf16 pass).
    tail_err = check(
        "tail_accumulate vs XLA scatter",
        float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))), 1e-5,
    )
    emit(
        phase="kernels", kernel="umap.tail_accumulate", rows=n, cols=d,
        neighbours=15, components=2, epochs=epochs, cold_s=cold, warm_s=warm,
        tail_rel_err=tail_err, tol={"tail_rel": 1e-5},
        tail_cfg=cfg._asdict(), route=route, **memory(),
    )


def run_serving(key, pca_model, km_model):
    """ServingRuntime in this process: warmed buckets, requests of 1, 7, 64
    and 1000 rows from a few threads, every answer checked against the
    model's own transform/predict, no compile after warm-up."""
    import jax
    import numpy as np

    from spark_rapids_ml_tpu.serving.server import ServingRuntime
    from spark_rapids_ml_tpu.utils import tracing

    d_pca = int(np.asarray(pca_model.pc).shape[0])
    centres = np.asarray(km_model.clusterCenters(), dtype=np.float32)
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    requests = {}
    for n in REQUEST_ROWS:
        # KMeans rows sit tight around the model's own centres: no row is a
        # near-tie, so a label cannot flip with the bucket it ran in.
        labels = rng.integers(0, centres.shape[0], n)
        requests[("pca", n)] = rng.normal(size=(n, d_pca)).astype(np.float32)
        requests[("kmeans", n)] = (
            centres[labels] + 0.01 * rng.normal(size=(n, centres.shape[1]))
        ).astype(np.float32)

    rt = ServingRuntime()
    try:
        t0 = time.perf_counter()
        rt.register("pca", pca_model, warm_buckets=WARM_BUCKETS, warm_dtype=np.float32)
        rt.register("kmeans", km_model, warm_buckets=WARM_BUCKETS, warm_dtype=np.float32)
        warm_s = round(time.perf_counter() - t0, 3)
        compiles = tracing.counter_value("serving.compile")
        expected = {
            key: np.asarray(
                pca_model.transform(x) if key[0] == "pca" else km_model.predict(x)
            )
            for key, x in requests.items()
        }
        errors, worst = [], [0.0]
        lock = threading.Lock()

        def client(tid: int) -> None:
            try:
                order = list(requests)
                for r in range(3):
                    for name, n in order[tid:] + order[:tid]:
                        got = np.asarray(
                            rt.submit(name, requests[(name, n)], timeout=120.0).result()
                        )
                        want = expected[(name, n)]
                        if got.shape != want.shape:
                            raise SmokeFailure(f"{name} x{n}: shape {got.shape} != {want.shape}")
                        if name == "kmeans":
                            if not np.array_equal(got, want):
                                raise SmokeFailure(f"kmeans x{n}: labels differ from predict")
                        else:
                            # Same kernel, same precision; a coalesced
                            # batch runs in a larger row bucket, which may
                            # tile the matmul differently: f32 rounding
                            # relative to the output's magnitude.
                            err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
                            with lock:
                                worst[0] = max(worst[0], err)
            except Exception as exc:  # raised again by the main thread
                errors.append(exc)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,)) for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        serve_s = round(time.perf_counter() - t0, 3)
        if errors:
            raise errors[0]
        pca_err = check("served pca vs transform", worst[0], 1e-5)
        after = tracing.counter_value("serving.compile")
        if after != compiles:
            raise SmokeFailure(f"serving.compile rose after warm-up: {compiles} -> {after}")
    finally:
        rt.close()
    emit(
        phase="serving", models=["pca", "kmeans"], request_rows=list(REQUEST_ROWS),
        threads=4, requests=4 * 3 * len(requests), warm_buckets=list(WARM_BUCKETS),
        warm_s=warm_s, serve_s=serve_s, compiles_at_warm=compiles,
        compiles_after=after, pca_rel_err=pca_err, tol={"pca_rel": 1e-5},
        kmeans_labels="equal", **memory(),
    )


def run_mesh(key, sz, eig, chips: int):
    """PCA(mesh=make_mesh((chips, 1))) on rows generated already sharded,
    against the plain reference on ALL rows (exact: proves the collective),
    and against the one-device fit and the reference on one shard's rows
    (sampling-level agreement)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    import spark_rapids_ml_tpu.linalg.row_matrix as row_matrix
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    if len(jax.devices()) != chips:
        raise SmokeFailure(f"--chips {chips} but jax sees {len(jax.devices())} devices")
    mesh = make_mesh((chips, 1))
    rows_sharded = NamedSharding(mesh, PartitionSpec(DATA_AXIS, None))
    n, d = chips * sz["mesh_rows_per_chip"], sz["pca_cols"]
    # A jitted generator with out_shardings: every device makes its own
    # rows; nothing is device_put from device 0.
    x = generate(make_pca_rows, key, n, d, out_shardings=rows_sharded)
    shards = [
        {"device": s.device.id, "shape": list(s.data.shape)} for s in x.addressable_shards
    ]
    per_device = every_device_memory()
    if len({s["device"] for s in shards}) != chips or any(
        s["shape"] != [n // chips, d] for s in shards
    ):
        raise SmokeFailure(f"rows are not spread over {chips} devices: {shards}")

    def fit_mesh():
        model = PCA(mesh=mesh).setK(K_PCA).fit(x)
        return model, (model._pc_raw, model._ev_raw)

    captured = []
    with spy(row_matrix, "_pca_fit_device", captured,
             lambda a, kw, out: (a, kw)) as real_fit:
        (model, _), cold = timed(fit_mesh)
        (model, _), warm = timed(fit_mesh)
    a, kw = captured[-1]
    if len(a[0].sharding.device_set) != chips:
        raise SmokeFailure("the fit program did not receive the sharded rows")
    text = real_fit.lower(*a, **kw).compile().as_text()
    if "all-reduce" not in text:
        raise SmokeFailure("the compiled mesh fit holds no all-reduce")

    # Exact: the plain reference over all rows (GSPMD partitions it too, but
    # it shares no code with the library's fit).
    pc_ref, ev_ref = eig(jax.jit(reference_cov)(x))
    ev_err, pc_err = pca_errors(model, pc_ref, ev_ref)
    check("mesh explainedVariance vs all-rows reference", ev_err, 1e-5)
    check("mesh pc vs all-rows reference", pc_err, 1e-3)

    # One shard's rows on one device: the library's single-device fit and
    # the reference. A quarter of the sample estimates the same planted
    # spectrum to ~sqrt(d / rows) = 3% of an eigenvalue against 9% gaps,
    # so these agree at the sampling level only (and would still agree if
    # the all-reduce were dropped — the all-rows reference is the proof).
    x0 = x.addressable_shards[0].data
    (one, _), one_s = timed(lambda: _fit_pca_device(x0))
    ev_one, pc_one = pca_errors(model, one._pc_raw, one._ev_raw)
    pc_sub, ev_sub = eig(jax.jit(reference_cov)(x0))
    ev_sub_err, pc_sub_err = pca_errors(model, pc_sub, ev_sub)
    ev_lib_err, pc_lib_err = pca_errors(one, pc_sub, ev_sub)
    check("one-shard fit vs its own reference (ev)", ev_lib_err, 1e-5)
    check("one-shard fit vs its own reference (pc)", pc_lib_err, 1e-3)
    check("mesh vs one-shard fit explainedVariance", ev_one, 2e-3)
    check("mesh vs one-shard reference explainedVariance", ev_sub_err, 2e-3)
    emit(
        phase="mesh_pca_fit", chips=chips, rows=n, cols=d, k=K_PCA,
        cold_s=cold, warm_s=warm, one_shard_fit_s=one_s,
        shards=shards, per_device_after_generation=per_device,
        per_device_after_fit=every_device_memory(),
        all_reduce_in_compiled_text=True,
        vs_all_rows_reference={"explained_variance_err": ev_err, "pc_err": pc_err},
        vs_one_shard_fit={"explained_variance_err": ev_one, "pc_err": pc_one},
        vs_one_shard_reference={"explained_variance_err": ev_sub_err, "pc_err": pc_sub_err},
        one_shard_fit_vs_its_reference={"explained_variance_err": ev_lib_err, "pc_err": pc_lib_err},
        tol={"exact_explained_variance": 1e-5, "exact_pc": 1e-3, "sampling_explained_variance": 2e-3},
    )


# --- driver --------------------------------------------------------------


def run(args) -> None:
    if args.rehearse:
        # A live admission gate for the rehearsal too: the CPU backend
        # reports no memory, which would switch it off.
        os.environ.setdefault("TPUML_FIT_MEM_BUDGET", str(64 << 30))
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        raise SmokeFailure(f"jax found no accelerator: platform is {platform!r}")

    from spark_rapids_ml_tpu.robustness.degrade import DegradationWarning, degrade_mode

    # Degradation stays off, and a fallback that fired anyway is a failure.
    if degrade_mode() != "off":
        raise SmokeFailure(f"TPUML_DEGRADE={degrade_mode()!r}: the smoke runs with it off")
    warnings.simplefilter("error", DegradationWarning)
    phase_device(args.rehearse)  # also places the compile cache, before any compile

    sz = TINY if args.rehearse else FULL
    key = jax.random.key(args.seed)
    k_pca, k_host, k_kern, k_serve, k_mesh = jax.random.split(key, 5)
    eig = jax.jit(partial(reference_eig, k=K_PCA))
    if args.chips == 4:
        run_mesh(k_mesh, sz, eig, 4)
        return
    pca_model = run_pca_fit(k_pca, sz, eig)
    run_host_fit(k_host, sz, eig, args.rehearse)
    km_model = run_kernels(k_kern, sz, args.rehearse)
    run_serving(k_serve, pca_model, km_model)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0, help="seed of every array the run makes")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs ONLY the mesh PCA fit and what it is compared with")
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at tiny sizes; never a pass (exits 1)")
    args = parser.parse_args()
    t0 = time.perf_counter()
    device, error, code = None, None, 0
    try:
        run(args)
        device = device_record()
        if args.rehearse:
            error, code = "rehearsal only: every phase went through, nothing ran on a chip", 1
    except Exception as exc:  # the script's boundary: report, then fail
        traceback.print_exc()
        error, code = f"{type(exc).__name__}: {exc}", 2 if args.rehearse else 1
        try:
            device = device_record()
        except Exception:  # no backend came up at all
            device = None
    sys.stderr.flush()
    emit(phase="total", seconds=round(time.perf_counter() - t0, 1), **native_state())
    if error is not None:
        emit(ok=False, error=error, device=device)
        return code
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
