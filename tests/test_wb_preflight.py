"""AOT window-batch preflight: estimates scale with the batch and the halving
search lands on the largest candidate under the budget — all without touching
device memory (compile-only)."""
import jax.numpy as jnp
import pytest

from edgellm_tpu.models import tiny_config
from edgellm_tpu.tools.wb_preflight import (estimate_sweep_peak_bytes,
                                            largest_fitting_window_batch)

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
KW = dict(max_length=32, tail=9, layer=1, codec="int4_token_select",
          n_ratios=3, dtype=jnp.float32)


def test_estimate_grows_with_batch():
    small = estimate_sweep_peak_bytes(CFG, 2, **KW)
    big = estimate_sweep_peak_bytes(CFG, 8, **KW)
    assert big["peak"] > small["peak"]
    assert big["hiddens_stack"] == 4 * small["hiddens_stack"]
    for key in ("stats_call", "suffix_call", "peak"):
        assert small[key] > 0


def test_halving_respects_budget():
    est8 = estimate_sweep_peak_bytes(CFG, 8, **KW)
    est2 = estimate_sweep_peak_bytes(CFG, 2, **KW)
    # budget between the 2- and 8-window peaks -> search must settle below 8
    budget = (est2["peak"] + est8["peak"]) // 2
    wb, est = largest_fitting_window_batch(CFG, 8, hbm_bytes=budget,
                                           budget_frac=1.0, **KW)
    assert wb < 8 and est["peak"] <= budget


def test_min_window_batch_floor():
    wb, _ = largest_fitting_window_batch(CFG, 8, hbm_bytes=1, budget_frac=1.0,
                                         **KW)
    assert wb == 1  # nothing fits: floor, never an infinite loop


def test_relevance_preflight_halves_to_fit():
    from edgellm_tpu.tools.wb_preflight import largest_fitting_relevance_batch

    big = largest_fitting_relevance_batch(CFG, 8, max_length=32,
                                          dtype=jnp.float32,
                                          hbm_bytes=1 << 40, budget_frac=1.0)
    assert big == 8  # everything fits under a huge budget
    tiny = largest_fitting_relevance_batch(CFG, 8, max_length=32,
                                           dtype=jnp.float32,
                                           hbm_bytes=1, budget_frac=1.0)
    assert tiny == 1


def test_token_sweep_preflight_uses_earliest_layer():
    """The shared sweep wrapper sizes the longest suffix (earliest layer) and
    the dedup-aware ratio axis; a generous budget keeps the requested batch."""
    from edgellm_tpu.tools.wb_preflight import preflight_token_sweep_batch

    wb = preflight_token_sweep_batch(CFG, 4, max_length=32, stride=8,
                                     layers_of_interest=[2, 1], ratios=[0, 0.5],
                                     dtype=jnp.float32, hbm_bytes=1 << 40,
                                     budget_frac=1.0)
    assert wb == 4
    tiny = preflight_token_sweep_batch(CFG, 4, max_length=32, stride=8,
                                       layers_of_interest=[2, 1],
                                       ratios=[0, 0.5], dtype=jnp.float32,
                                       hbm_bytes=1, budget_frac=1.0)
    assert tiny == 1


def test_budget_comes_from_the_device_not_an_assumed_chip(monkeypatch):
    """With no explicit hbm_bytes the limit is what the device reports; a
    device that reports none is an error, never an assumed 15.75 GiB."""
    import jax

    from edgellm_tpu.tools import wb_preflight

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "devices", lambda: [_Dev({"bytes_limit": 1000})])
    assert wb_preflight._budget_bytes(None, 0.5) == 500
    assert wb_preflight._budget_bytes(64, 0.5) == 32  # explicit wins
    monkeypatch.setattr(jax, "devices", lambda: [_Dev(None)])
    with pytest.raises(RuntimeError, match="reports no memory limit"):
        wb_preflight._budget_bytes(None, 0.5)
