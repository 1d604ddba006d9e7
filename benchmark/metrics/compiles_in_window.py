"""JAX backend compiles that happened inside the batcher's ``submit()`` /
``step()`` during the window (the batcher's ``compiles`` counter, any jit's:
``jit_misses`` sees the step executable only). The benchmark prints its own
count of the same events beside it; both should be 0."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    return r1["compiles"] - r0["compiles"] if "compiles" in r0 else None
