"""Host time in the admission loop per admitted stream, over the window
(``admit_s`` / ``admitted``): what one admission holds every running slot up
by."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "admit_s", "admitted")
