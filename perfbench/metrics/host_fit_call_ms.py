from perfbench.metrics.fit_call_ms import read  # noqa: F401  (the same reader, moving the host cells' rate)
