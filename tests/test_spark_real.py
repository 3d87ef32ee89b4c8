"""The SAME adapter contract suite, against GENUINE pyspark: skipped wherever pyspark is not installed (this
CI image), and the complete proof the day an environment has it.

Smoke procedure for such an environment (documented here AND in
README.md):

    pip install "pyspark>=3.4,<4.0"
    python -m pytest tests/test_spark_real.py -q

Every assertion is shared with ``tests/test_spark_adapter.py`` via
``tests/spark_contract_suite.py`` — a behavior the stub models wrongly
shows up here as a real-cluster failure of the identical test. Tests
that instrument stub internals (the driver-fetch counter) self-skip.
"""

import pytest

pyspark = pytest.importorskip("pyspark")

import spark_contract_suite as _suite  # noqa: E402 - after importorskip

# Pull EVERY Test* class from the shared suite into this module's
# namespace so pytest collects it here — programmatic, so a class added
# to the suite can never be silently dropped by a stale import list.
for _name in dir(_suite):
    if _name.startswith("Test"):
        globals()[_name] = getattr(_suite, _name)

pytestmark = pytest.mark.spark


@pytest.fixture(scope="module")
def spark_env():
    """Genuine local[2] SparkSession + the adapter imported against real
    pyspark. Arrow is enabled for pandas_udf exchange (the production
    configuration; pyspark 3.5 'Apache Arrow in PySpark' guide)."""
    import importlib

    import spark_rapids_ml_tpu.spark.adapter as adapter

    adapter = importlib.reload(adapter)
    assert adapter.HAS_PYSPARK, "pyspark import failed inside the adapter"
    from pyspark.sql import SparkSession

    spark = (
        SparkSession.builder.master("local[2]")
        .appName("spark-rapids-ml-tpu-contract")
        .config("spark.sql.execution.arrow.pyspark.enabled", "true")
        .getOrCreate()
    )
    try:
        yield adapter, spark
    finally:
        spark.stop()
