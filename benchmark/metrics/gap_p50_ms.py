"""Median gap between tokens (see ``gap_mean_ms``)."""
from benchmark.latency import gaps_ms, pct


def read(record: dict):
    return pct(gaps_ms(record), 50)
