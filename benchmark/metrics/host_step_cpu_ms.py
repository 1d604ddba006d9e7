"""The scheduler thread's CPU time per decode step inside ``step()``
(``time.thread_time`` over what ``step_wall_s`` encloses: the batcher's
``step_cpu_s`` over the window), beside ``step_wall_ms``: the wall less this
is what the thread waited (for the device, for a lock) or was descheduled."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "step_cpu_s", "steps")
