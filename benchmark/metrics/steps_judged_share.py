"""The share of the window's launched steps that had an expectation to be
held against, in percent: ``report()``'s ``steps_judged`` over ``steps``,
differenced over the window. A step is judged once its kind (the launched
steps with the same admissions, prefill tokens and "did it evict") has five
walls; how much of the window ``stalls_in_window`` can speak for. None where
the program keeps no such count, or the window launched nothing."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "steps_judged" not in r0 or "steps_judged" not in r1:
        return None
    steps = r1["steps"] - r0["steps"]
    if not steps:
        return None
    return 100.0 * (r1["steps_judged"] - r0["steps_judged"]) / steps
