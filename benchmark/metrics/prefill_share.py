"""The batcher's own ``prefill_s`` clock over the window, as a share of it."""
from benchmark.latency import delta


def read(record: dict):
    return 100.0 * delta(record, "prefill_s") / record["window_s"]
