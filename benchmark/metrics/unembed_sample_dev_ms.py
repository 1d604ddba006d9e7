"""Device time per decode step under the ``unembed_sample`` scope: the final
norm, the head over the whole vocabulary and the per-slot sampler over the
float32 logits. From the program table's scope sums / ``batch.step`` spans;
None where the program has no such scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("unembed_sample",), STEP_SPAN)
