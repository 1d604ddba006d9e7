"""Streams the batcher evicted for pages during the window."""
from benchmark.latency import delta


def read(record: dict):
    return float(delta(record, "evicted"))
