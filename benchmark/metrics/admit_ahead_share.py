"""The share of the window's fresh admissions whose token 0 was still on the
device when the device work after them was dispatched, in percent:
``report()``'s ``admits_ahead`` over ``admitted``, differenced over the
window. The batcher reads an admission's token 0 one admission behind the
dispatch inside its admit loop, and the loop's last one behind the step's
launch, so the device has the next prefill or the step queued while the host
waits for the token; an admission drained early (an eviction in the call that
admitted it, a call that launched nothing) is the old order, and leaves the
device idle through the host's next dispatch. A resume has no token 0 and
counts as admitted. None where the program has no such counter, or the window
admitted nothing."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "admits_ahead" not in r0 or "admits_ahead" not in r1:
        return None
    admitted = r1["admitted"] - r0["admitted"]
    if not admitted:
        return None
    return 100.0 * (r1["admits_ahead"] - r0["admits_ahead"]) / admitted
