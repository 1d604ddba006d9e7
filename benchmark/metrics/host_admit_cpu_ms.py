"""The scheduler thread's CPU time per decode step in ``batch.step.admit``
(``time.thread_time`` over what ``admit_s`` encloses: the batcher's
``admit_cpu_s`` over the window), beside ``host_admit_ms``: that wall holds
the host's dispatch of the admissions and its waits on their token 0s alike,
this is the dispatch alone. Near ``host_admit_ms``: the host is the longer
side of an admitting call; far under it: the device is."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "admit_cpu_s", "steps")
