"""Mean wall of a launched step that admitted nothing: the wall of
``step_wall_hist`` (its six phase columns summed) less ``admit_step_wall_s``,
over its steps less ``admit_steps``. A call that found nothing to run is in
neither. This less ``step_dev_ms`` is the host's hand-off around a step."""
from benchmark import step_wall_hist
from benchmark.latency import delta


def read(record: dict):
    table = step_wall_hist.window(record)
    if table is None:
        return None
    plain = step_wall_hist.steps(table[0]) - delta(record, "admit_steps")
    if not plain:
        return None
    return 1e3 * (step_wall_hist.wall_s(table[0])
                  - delta(record, "admit_step_wall_s")) / plain
