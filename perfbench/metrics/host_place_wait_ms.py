from perfbench.metrics._stages import per_fit


def read(ctx):
    waited = per_fit(ctx, "ingest.place.wait_ns")
    return None if waited is None else waited / 1e6
