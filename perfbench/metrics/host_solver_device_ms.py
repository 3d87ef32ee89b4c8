from perfbench.metrics.solver_device_ms import read  # noqa: F401  (the same reader, moving the host cells' rate)
