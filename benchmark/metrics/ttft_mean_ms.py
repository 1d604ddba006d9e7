"""Due time to first token visible to the client, mean over every request
due in the window."""
from benchmark.latency import mean, ttfts_ms


def read(record: dict):
    return mean(ttfts_ms(record))
