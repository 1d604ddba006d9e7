"""Mean wall of the slowest 1% of the window's launched steps, from the
batcher's table of steps by wall (``step_wall_hist``): whole rows from the top
down, the boundary row pro rata. A mean over a tail moves by a step's weight
when one more step crosses the boundary, where a percentile jumps."""
from benchmark import step_wall_hist


def read(record: dict):
    table = step_wall_hist.window(record)
    if table is None:
        return None
    steps, wall, _ = step_wall_hist.tail(table[0], 0.01)
    return 1e3 * wall / steps
