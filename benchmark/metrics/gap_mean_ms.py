"""Mean time between consecutive tokens of a stream, over every token
stamped in the window."""
from benchmark.latency import gaps_ms, mean


def read(record: dict):
    return mean(gaps_ms(record))
