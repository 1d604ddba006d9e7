"""The batcher's ``decode_s`` clock (launch to host sync of the sampled
tokens) per step, over the window."""
from benchmark.latency import delta


def read(record: dict):
    return 1e3 * delta(record, "decode_s") / delta(record, "steps") if delta(record, "steps") else None
