"""The share of the window's launched steps that admitted at least one
stream (``admit_steps`` over the steps of ``step_wall_hist``), in percent."""
from benchmark import step_wall_hist
from benchmark.latency import delta


def read(record: dict):
    table = step_wall_hist.window(record)
    if table is None:
        return None
    return 100.0 * delta(record, "admit_steps") / step_wall_hist.steps(
        table[0])
