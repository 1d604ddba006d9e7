"""Device time in the Pallas prefill attention kernel as a share of the
device's busy time, from the trace. The kernel is named by the configuration
file (``program.prefill_kernel``)."""


def read(record: dict):
    tr = record.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    key = record["config"]["program"]["prefill_kernel"]
    t = sum(s for name, s in tr["ops"].items() if key in name)
    return 100.0 * t / tr["busy_s"]
