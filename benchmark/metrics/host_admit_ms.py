"""Host time per decode step in ``batch.step.admit`` (the batcher's ``admit_s``
clock over the window): the admission loop: for every stream admitted, the
prefill's dispatch, the adopt's dispatch and the host sync on its token 0."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "admit_s", "steps")
