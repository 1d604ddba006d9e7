"""Assignments that chose an identity expert (no weights, ``E(u) = u``) over
assignments made: ``report()``'s ``zero_assignments`` / ``routed_assignments``
differenced over the window. The step pays for none of them. None where the
program has no such counter (another family, or a program older than it)."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "zero_assignments" not in r1 or "routed_assignments" not in r1:
        return None
    made = r1["routed_assignments"] - r0.get("routed_assignments", 0)
    if not made:
        return None
    return 100.0 * (r1["zero_assignments"]
                    - r0.get("zero_assignments", 0)) / made
