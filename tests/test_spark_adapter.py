"""End-to-end pyspark adapter tests against the contract stub.

The CI image has no pyspark; ``tests/pyspark_stub`` implements the exact
API surface the adapter consumes (with real partition semantics and
cloudpickle serialization boundaries), so every line of
``spark_rapids_ml_tpu.spark.adapter`` executes here — fit on an RDD with
mapPartitions/treeReduce, Arrow-batch pandas_udf transforms, and
save/load round-trips. The test
classes live in ``tests/spark_contract_suite.py`` and are shared with
``tests/test_spark_real.py``, which runs the same assertions against
genuine pyspark when installed.
"""

import importlib
import os
import sys

import pytest

import spark_contract_suite as _suite

# Pull EVERY Test* class from the shared suite into this module's
# namespace so pytest collects it here — programmatic, so a class added
# to the suite can never be silently dropped by a stale import list.
for _name in dir(_suite):
    if _name.startswith("Test"):
        globals()[_name] = getattr(_suite, _name)

_STUB = os.path.join(os.path.dirname(os.path.abspath(__file__)), "pyspark_stub")

pytestmark = pytest.mark.spark


@pytest.fixture(scope="module")
def spark_env():
    """Install the pyspark stub, (re)import the adapter against it, and
    hand back (adapter_module, SparkSession). Restores sys state after."""
    had_real = "pyspark" in sys.modules
    saved = {
        name: mod for name, mod in sys.modules.items() if name.startswith("pyspark")
    }
    for name in list(saved):
        del sys.modules[name]
    sys.path.insert(0, _STUB)
    adapter_was = sys.modules.pop("spark_rapids_ml_tpu.spark.adapter", None)
    try:
        adapter = importlib.import_module("spark_rapids_ml_tpu.spark.adapter")
        assert adapter.HAS_PYSPARK, "stub failed to import as pyspark"
        from pyspark.sql import SparkSession

        yield adapter, SparkSession.builder.master("local[2]").getOrCreate()
    finally:
        sys.path.remove(_STUB)
        for name in [n for n in sys.modules if n.startswith("pyspark")]:
            del sys.modules[name]
        sys.modules.update(saved)
        if adapter_was is not None and not had_real:
            sys.modules["spark_rapids_ml_tpu.spark.adapter"] = adapter_was
        else:
            sys.modules.pop("spark_rapids_ml_tpu.spark.adapter", None)
