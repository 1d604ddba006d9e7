"""Assignments that landed on an expert held here over assignments made
(``report()``'s ``routed_local`` / ``routed_assignments`` over the window):
what an expert-parallel chip keeps of its own tokens' routing."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "routed_assignments" not in r1:
        return None
    made = r1["routed_assignments"] - r0["routed_assignments"]
    if not made:
        return None
    return 100.0 * (r1["routed_local"] - r0["routed_local"]) / made
