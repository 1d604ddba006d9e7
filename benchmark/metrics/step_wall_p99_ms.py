"""The wall of ``ContinuousBatcher.step()`` under which 99% of the window's
launched steps lie, from the batcher's own table of steps by wall
(``step_wall_hist``), interpolated inside the bucket (no bucket is wider than
19%). A percentile sits on one side or the other of a cliff:
``tail_step_wall_ms`` is the mean beyond it."""
from benchmark import step_wall_hist


def read(record: dict):
    table = step_wall_hist.window(record)
    if table is None:
        return None
    return 1e3 * step_wall_hist.percentile_s(*table, 0.99)
