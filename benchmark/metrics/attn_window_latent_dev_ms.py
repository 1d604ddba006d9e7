"""Device time per decode step under the ``attn.window_latent`` scope and the
ring's row write within it (``attn.window_latent.write``): every window
latent layer's projections, norms and rotation, the ring write, the read of
the ring's rows under the band (the page walk, or the gather), ``W_kvb``'s V
half, the heads' gate and ``W_o``. Only operations that ran inside the step
executable count (``rooflines_lfm2_moe.step_scope_ms``). None where the
program has no such scope."""
from benchmark.rooflines_dots3_note import WINDOW_LATENT_SCOPES
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    return step_scope_ms(record, WINDOW_LATENT_SCOPES)
