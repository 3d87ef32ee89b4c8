"""Test harness configuration.

The reference's tests run on local[*] Spark with 2 RDD partitions standing in
for "distributed" (SURVEY.md §4). Here the analogue is a virtual 8-device CPU
mesh (xla_force_host_platform_device_count), which exercises the real sharded
code path — psum/all_gather collectives included — without TPU hardware, plus
x64 so the fp64 oracle tolerance (absTol 1e-5, PCASuite.scala:71) is
meaningful.
"""

import os

# Force the CPU platform for tests (the env may pre-select a TPU platform);
# set SPARK_TPU_ML_TEST_PLATFORM to override, e.g. to run the suite on-chip.
_platform = os.environ.get("SPARK_TPU_ML_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# JAX_PLATFORMS is read when jax is first imported; something earlier in
# the process (a pytest plugin) may already have done that, so pin the
# platform via config as well.
jax.config.update("jax_platforms", _platform)
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: DISABLED (r5). XLA:CPU's executable
# (de)serialization is not reliable on this jaxlib: a cache populated by an
# earlier host SIGABRTed inside `compilation_cache.get_executable_and_time`
# ("Loading XLA:CPU AOT result. Target machine feature +prefer-no-scatter is
# not supported on the host machine" escalating from warning to abort), and
# even a FRESH cache segfaulted inside `put_executable_and_time` while
# serializing one of the L-BFGS while_loop executables — both ~96% into the
# suite, both unattributable to library code. Recompiling every run costs
# a few minutes; a mid-suite SIGSEGV costs the whole run. Re-enable only
# after jaxlib's CPU AOT serializer stabilizes, and key the directory by
# the host CPU flags if you do (cross-host replay was the first crash).

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def chip_dtypes():
    """The chip's configuration: x64 off, so the compute dtype is float32."""
    with jax.enable_x64(False):
        yield


# The 3x5 synthetic dataset from the reference suite (PCASuite.scala:42-46):
# one all-zero sparse row, one sparse row, one dense row.
REFERENCE_DATA = [
    ("sparse_zero", 5, [], []),
    ("sparse", 5, [1, 3], [1.0, 7.0]),
    ("dense", [2.0, 0.0, 3.0, 4.0, 5.0], None, None),
]


@pytest.fixture
def reference_rows():
    from spark_rapids_ml_tpu.core.data import Vectors

    return [
        Vectors.sparse(5, [], []),
        Vectors.sparse(5, [1, 3], [1.0, 7.0]),
        Vectors.dense(2.0, 0.0, 3.0, 4.0, 5.0),
    ]


def numpy_pca_oracle(x: np.ndarray, k: int):
    """CPU ground truth — the Spark mllib RowMatrix oracle analogue
    (PCASuite.scala:50-52): eigendecomposition of the sample covariance.
    """
    x = np.asarray(x, dtype=np.float64)
    n = x.shape[0]
    mean = x.mean(axis=0)
    b = x - mean
    cov = b.T @ b / (n - 1)
    # SVD of the symmetric PSD covariance (LAPACK, like breeze brzSvd in the
    # mllib oracle): singular values are its eigenvalues, descending. Using
    # LAPACK SVD on both sides keeps rank-deficient cases (null-space basis
    # is arbitrary) comparable — same reason the reference suite passes.
    v, w, _ = np.linalg.svd(cov)
    # deterministic sign flip: largest-|.| element of each column positive
    idx = np.argmax(np.abs(v), axis=0)
    signs = np.where(v[idx, np.arange(v.shape[1])] < 0, -1.0, 1.0)
    v = v * signs
    total = np.clip(w, 0, None).sum()
    explained = np.clip(w, 0, None) / total if total > 0 else w
    return v[:, :k], explained[:k]


# File-logging analogue of the reference's log4j.properties (SURVEY.md §2:
# tests append to target/unit-tests.log): jax/absl and framework loggers
# write to target/unit-tests.log so failing CI runs keep a artifact trail.
import logging as _logging
import pathlib as _pathlib

_log_dir = _pathlib.Path(__file__).resolve().parent.parent / "target"
_log_dir.mkdir(exist_ok=True)
_root = _logging.getLogger()
if not any(
    isinstance(h, _logging.FileHandler)
    and getattr(h, "baseFilename", "").endswith("unit-tests.log")
    for h in _root.handlers
):
    _handler = _logging.FileHandler(_log_dir / "unit-tests.log")
    _handler.setFormatter(
        _logging.Formatter("%(asctime)s %(levelname).1s %(name)s: %(message)s")
    )
    _root.addHandler(_handler)
    if _root.level in (_logging.NOTSET, _logging.WARNING):
        # INFO so the jax/absl trail actually reaches the file (the
        # default WARNING threshold would filter the records this
        # artifact exists to keep); pytest still captures console output.
        _root.setLevel(_logging.INFO)


# Bound cumulative in-process XLA state: after ~480 tests in ONE process,
# XLA:CPU's compiler segfaulted compiling a routine logistic-fit program
# (reproduced 3x at the same suite position with the persistent cache
# reading, writing, and fully disabled — the crash is in
# backend_compile_and_load itself, not the cache). Split halves of the
# suite never crash, so the trigger is accumulated executables/live
# buffers. Clearing jax's caches between test MODULES frees compiled
# programs (tests are module-local; cross-module recompiles are a few
# seconds) and keeps the resident state far below the crash region.
@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    yield
    jax.clear_caches()


# Stage spans (utils/tracing.py::StageRange) are leaves: the sum of their
# durations is held against a fit's wall time, which a stage opened inside
# a stage on the same thread would count twice. Production pays nothing
# for the rule; here every fit of every test is held to it.
@pytest.fixture(autouse=True)
def stages_never_nest(monkeypatch):
    import threading

    from spark_rapids_ml_tpu.utils import tracing

    open_stage = threading.local()
    enter, leave = tracing.StageRange.__enter__, tracing.StageRange.__exit__

    def checked_enter(self):
        outer = getattr(open_stage, "name", None)
        assert outer is None, f"stage {self.name!r} opened inside stage {outer!r}"
        open_stage.name = self.name
        return enter(self)

    def checked_exit(self, exc_type=None, exc=None, tb=None):
        open_stage.name = None
        return leave(self, exc_type, exc, tb)

    monkeypatch.setattr(tracing.StageRange, "__enter__", checked_enter)
    monkeypatch.setattr(tracing.StageRange, "__exit__", checked_exit)
