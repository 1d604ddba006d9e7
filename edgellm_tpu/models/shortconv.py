"""The gated short convolution of the ``lfm2_moe`` family, in plain JAX.

One layer keeps, per sequence, a window: the last ``L - 1`` rows (``L`` =
``cfg.conv_window`` taps) of a product ``z = B * x``, ``D`` lanes each, and
nothing else: no matrix state, nothing that grows with the stream::

    [B | C | x] = u W_in                       (W_in D x 3D, no bias)
    z_t = B_t * x_t
    c_t = sum_{j < L} w[:, j] * z_{t-(L-1)+j}  (z_s = 0 for s < 0; tap L-1
                                                on the current position; no
                                                activation)
    y_t = (C_t * c_t) W_out

:func:`shortconv_prefill` runs whole sequences and hands on the window as of
the last position (a prompt shorter than ``L - 1`` leaves zero rows ahead of
its own); :func:`shortconv_step` is the one-position update the decode step
runs against that window. The window is float32 whatever the weights' type,
as the state store keeps Mamba-2's (``models/mamba2.py``).

Scopes (``obs/names.py``): ``shortconv.proj`` the two projections,
``shortconv.conv`` the taps, the gates and the window's update.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .configs import ModelConfig


def _chunks(cfg: ModelConfig, bcx):
    """(..., 3D) -> B, C, x, float32."""
    d = cfg.hidden_size
    bcx = bcx.astype(jnp.float32)
    return bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]


def shortconv_prefill(cfg: ModelConfig, lp: dict, u: jnp.ndarray):
    """One layer over whole sequences: u (B, S, D) normalised input -> (out
    (B, S, D) in u's dtype, window (B, L-1, D) f32 as of the last
    position)."""
    s, k = u.shape[1], cfg.conv_window
    with jax.named_scope("shortconv.proj"):
        bcx = u @ lp["w_in"]
    with jax.named_scope("shortconv.conv"):
        gate_b, gate_c, x = _chunks(cfg, bcx)
        padded = jnp.pad(gate_b * x, ((0, 0), (k - 1, 0), (0, 0)))
        window = padded[:, s:]                     # the last k-1 rows
        w = lp["conv_w"].astype(jnp.float32)       # (D, k)
        conv = sum(padded[:, j:j + s] * w[:, j] for j in range(k))
        y = (gate_c * conv).astype(u.dtype)
    with jax.named_scope("shortconv.proj"):
        return y @ lp["w_out"], window


def shortconv_step(cfg: ModelConfig, lp: dict, u: jnp.ndarray,
                   window: jnp.ndarray):
    """One position for every row: u (B, D) normalised input, window (B,
    L-1, D) f32 -> (out (B, D), window)."""
    with jax.named_scope("shortconv.proj"):
        bcx = u @ lp["w_in"]
    with jax.named_scope("shortconv.conv"):
        gate_b, gate_c, x = _chunks(cfg, bcx)
        rows = jnp.concatenate([window, (gate_b * x)[:, None]], axis=1)
        # a product over the taps, not a sum of shifted slices: the compiled
        # step then reads the window once into ``rows`` and writes the store
        # where it lies (as a sum of slices the v5e's compiler copies the
        # whole store twice: tests/test_chip_compile.py)
        conv = jnp.einsum("bkd,dk->bd", rows,
                          lp["conv_w"].astype(jnp.float32))
        y = (gate_c * conv).astype(u.dtype)
    with jax.named_scope("shortconv.proj"):
        return y @ lp["w_out"], rows[:, 1:]
