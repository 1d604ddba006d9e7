"""Mean time an admitted stream spent in the batcher's waiting queue, from
``submit()`` (or its eviction) to the start of its admission, over the window
(``queue_wait_s`` / ``admitted``)."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "queue_wait_s", "admitted")
