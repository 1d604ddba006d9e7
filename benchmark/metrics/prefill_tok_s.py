"""Prompt positions prefilled a second of ``prefill_s`` (``prefill_tokens`` /
``prefill_s`` over the window), on the host's clock: the rate at which an
admission gets through its prompt while every running stream waits."""
from benchmark.latency import delta


def read(record: dict):
    if "prefill_tokens" not in record["report0"]:
        return None
    seconds = delta(record, "prefill_s")
    return delta(record, "prefill_tokens") / seconds if seconds else None
