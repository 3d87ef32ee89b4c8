from perfbench.metrics import ingest_ms
from perfbench.metrics._common import work


def read(ctx):
    ms = ingest_ms.read(ctx)
    if not ms:
        return None
    return work(ctx)["host_bytes"] / 1e9 / (ms / 1e3)
