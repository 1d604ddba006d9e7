"""The share of the window's launched steps that were launched while the
step before them was still unread, in percent: ``report()``'s ``steps_ahead``
over ``steps``, differenced over the window. The batcher launches step N+1
before it reads step N's tokens, so the device has its next step queued while
the host reads and commits; a launch that found nothing in flight (the first
after an idle batcher, after an eviction's drain, after a hand-off) is the
old order, and waits the host out. None where the program has no such
counter, or the window launched no step."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "steps_ahead" not in r0 or "steps_ahead" not in r1:
        return None
    steps = r1["steps"] - r0["steps"]
    if not steps:
        return None
    return 100.0 * (r1["steps_ahead"] - r0["steps_ahead"]) / steps
