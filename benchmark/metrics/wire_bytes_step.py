"""Bytes one decode step puts on the wire, summed over the cuts: the split
runtime's ``decode_hop_bytes(max_slots)``, a count from shapes."""


def read(record: dict):
    return None if record.get("wire_bytes_step") is None else float(record["wire_bytes_step"])
