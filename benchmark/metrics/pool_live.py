"""Live KV positions over the pool's capacity, sampled before every step of
the window (``unique_live_tokens / token_capacity``)."""


def read(record: dict):
    return 100.0 * record["pool_live_share"]
