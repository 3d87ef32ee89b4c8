from perfbench.metrics.fit_rows_per_s import read  # noqa: F401  (the same quantity, a host cell's bound)
