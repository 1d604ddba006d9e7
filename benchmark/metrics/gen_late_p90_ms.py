"""How late the generator submitted (submit stamp minus due time), 90th
percentile: one thread steps and submits, so a request waits for the running
step to end."""
from benchmark.latency import late_ms, pct


def read(record: dict):
    return pct(late_ms(record), 90)
