"""Median time to first token (see ``ttft_mean_ms``)."""
from benchmark.latency import pct, ttfts_ms


def read(record: dict):
    return pct(ttfts_ms(record), 50)
