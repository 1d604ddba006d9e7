"""The share of the slowest 1% of steps' wall that is the admission loop
(``admit_s`` of ``tail_step_wall_ms``'s steps, from the same rows of
``step_wall_hist``), in percent: what of the tail an inline prefill makes."""
from benchmark import step_wall_hist


def read(record: dict):
    table = step_wall_hist.window(record)
    if table is None:
        return None
    _, wall, admit = step_wall_hist.tail(table[0], 0.01)
    return 100.0 * admit / wall if wall else None
