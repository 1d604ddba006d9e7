"""How long a first token lay on the host before anyone could see it, per
admitted request: from the reading after ``batch.admit.tok0_sync`` to the
return of the ``step()`` call that admitted the stream (``tok0_hold_s`` /
``admitted``), the decode step of the same call between them."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "tok0_hold_s", "admitted")
