"""The busiest held expert's assignments over the mean held expert's, within
a layer, worst layer: ``report()``'s ``expert_tokens`` differenced over the
window. 1.0 is a flat load; the grouped products wait for the busiest."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "expert_tokens" not in r1:
        return None
    worst = None
    for row0, row1 in zip(r0["expert_tokens"], r1["expert_tokens"]):
        layer = [b - a for a, b in zip(row0, row1)]
        if sum(layer):
            skew = max(layer) * len(layer) / sum(layer)
            worst = skew if worst is None else max(worst, skew)
    return worst
