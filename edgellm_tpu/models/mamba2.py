"""The Mamba-2 mixer of the ``granitemoehybrid`` family, in plain JAX.

One layer keeps, per sequence, a convolution window (the last ``d_conv - 1``
pre-convolution ``xBC`` columns) and a recurrent state ``H`` of
``heads x head_dim x d_state`` floats that every position overwrites::

    [z | xBC | dt] = u W_in
    xBC = silu(causal_depthwise_conv1d(xBC; w_c) + b_c) -> x, B, C
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t;   y_t = H_t C_t + D x_t
    out = rms(y * silu(z); w_n) W_out

:func:`mamba2_prefill` runs a whole prompt in chunks of ``cfg.mamba_chunk``
positions (the SSD form: a masked matmul inside a chunk, the recurrence only
between chunks) and hands on ``(conv_state, ssm_state)``; :func:`mamba2_step`
is the literal one-position recurrence the decode step runs against that
state. Both keep and update the recurrent state and the window in float32
whatever the weights' type: the recurrence runs for thousands of steps, and
a bfloat16 state loses a position's contribution as soon as the state is 256
times larger than it.

Scopes (``obs/names.py``): ``ssm.proj`` the two projections, ``ssm.scan``
the chunked prefill core, ``ssm.step`` the decode update (window, recurrence,
gated norm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .configs import ModelConfig


def _split_proj(cfg: ModelConfig, zxbcdt):
    """(..., 2*d_inner + 2*G*N + H) -> z, xBC, dt."""
    di, cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    return (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
            zxbcdt[..., di + cd:])


def _split_xbc(cfg: ModelConfig, xbc):
    """(..., conv_dim) -> x (..., H, P), B (..., G, N), C (..., G, N)."""
    di, gn = cfg.mamba_d_inner, cfg.mamba_n_groups * cfg.mamba_d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim)
    b = xbc[..., di:di + gn].reshape(*lead, cfg.mamba_n_groups,
                                     cfg.mamba_d_state)
    c = xbc[..., di + gn:].reshape(*lead, cfg.mamba_n_groups,
                                   cfg.mamba_d_state)
    return x, b, c


def _to_heads(cfg: ModelConfig, bc):
    """B or C (..., G, N) -> (..., H, N): each group serves H/G heads."""
    return jnp.repeat(bc, cfg.mamba_heads // cfg.mamba_n_groups, axis=-2)


def _gated_norm(cfg: ModelConfig, y, z, scale):
    """rms(y * silu(z); scale) over each of the ``n_groups`` groups of the
    d_inner axis (gate first, then norm), in float32."""
    g = cfg.mamba_n_groups
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    vg = v.reshape(*v.shape[:-1], g, v.shape[-1] // g)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True)
                            + cfg.norm_eps)
    return vg.reshape(v.shape) * scale.astype(jnp.float32)


def _ssd_chunked(cfg: ModelConfig, x, dt, a, b, c, h0):
    """The chunked scan for ONE sequence, float32 throughout.

    x (S, H, P), dt (S, H) post-softplus, a (H,) negative, b/c (S, G, N),
    h0 (H, P, N). S is a multiple of the chunk. Returns (y (S, H, P) without
    the D skip, final state (H, P, N)). A position with dt == 0 leaves the
    state as it found it, which is how the caller pads.

    Heads are kept as (group, head in group) so that B and C are multiplied
    once a group, and every contraction is written as a two-operand batched
    matmul: a three-operand einsum here is free to materialise a
    (chunks, Q, H, P, N) intermediate, 4 GB at the published sizes."""
    s, h, p = x.shape
    g, n = b.shape[-2:]
    q = min(cfg.mamba_chunk, s)
    nc, hg = s // q, h // g

    def heads_first(t, tail):        # (S, H, ...) -> (nc, G, hg, Q, ...)
        return jnp.moveaxis(t.reshape(nc, q, g, hg, *tail), 1, 3)

    xd = heads_first(x * dt[..., None], (p,))                 # (nc,G,hg,Q,P)
    cum = jnp.cumsum(heads_first(dt * a, ()), axis=-1)        # (nc,G,hg,Q)
    bq = jnp.moveaxis(b.reshape(nc, q, g, n), 1, 2)           # (nc,G,Q,N)
    cq = jnp.moveaxis(c.reshape(nc, q, g, n), 1, 2)
    # inside a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) xd_s
    tri = jnp.tril(jnp.ones((q, q), bool))
    seg = cum[..., :, None] - cum[..., None, :]               # (nc,G,hg,t,s)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = jnp.einsum("cgtn,cgsn->cgts", cq, bq)
    y = jnp.einsum("cghts,cghsp->cghtp", cb[:, :, None] * decay, xd)
    # what each chunk adds to the state it ends with
    to_end = jnp.exp(cum[..., -1:] - cum)                     # (nc,G,hg,Q)
    chunk_states = jnp.einsum("cghsp,cgsn->cghpn", xd * to_end[..., None],
                              bq)
    chunk_decay = jnp.exp(cum[..., -1])                       # (nc,G,hg)

    def carry(hprev, xs):
        st, dec = xs
        return dec[..., None, None] * hprev + st, hprev

    h_final, h_in = jax.lax.scan(carry, h0.reshape(g, hg, p, n),
                                 (chunk_states, chunk_decay))
    # the state a chunk started from, seen from each of its positions
    y = y + (jnp.einsum("cgtn,cghpn->cghtp", cq, h_in)
             * jnp.exp(cum)[..., None])
    return (jnp.moveaxis(y, 3, 1).reshape(s, h, p),
            h_final.reshape(h, p, n))


def mamba2_prefill(cfg: ModelConfig, lp: dict, u: jnp.ndarray):
    """One Mamba-2 layer over whole sequences: u (B, S, D) normalised input
    -> (out (B, S, D) in u's dtype, conv_state (B, d_conv-1, conv_dim) f32,
    ssm_state (B, H, P, N) f32), both states as of the last position."""
    bsz, s, _ = u.shape
    k = cfg.mamba_d_conv
    with jax.named_scope("ssm.proj"):
        z, xbc, dt = _split_proj(cfg, u @ lp["w_in"])
    with jax.named_scope("ssm.scan"):
        xbc = xbc.astype(jnp.float32)
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv_state = padded[:, s:]                 # the last k-1 columns
        w = lp["conv_w"].astype(jnp.float32)       # (conv_dim, k)
        conv = sum(padded[:, j:j + s] * w[:, j] for j in range(k))
        xbc = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))
        x, bm, cm = _split_xbc(cfg, xbc)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
        q = min(cfg.mamba_chunk, s)
        pad = -s % q
        if pad:  # dt = 0 there: the state passes through untouched
            x, bm, cm, dt = (jnp.pad(t, ((0, 0), (0, pad))
                                     + ((0, 0),) * (t.ndim - 2))
                             for t in (x, bm, cm, dt))
        h0 = jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim,
                        cfg.mamba_d_state), jnp.float32)
        y, ssm_state = jax.vmap(
            lambda x_, dt_, b_, c_: _ssd_chunked(cfg, x_, dt_, a, b_, c_, h0)
        )(x, dt, bm, cm)
        y = y[:, :s] + lp["D"].astype(jnp.float32)[:, None] * x[:, :s]
        y = _gated_norm(cfg, y.reshape(bsz, s, cfg.mamba_d_inner), z,
                        lp["norm_scale"]).astype(u.dtype)
    with jax.named_scope("ssm.proj"):
        return y @ lp["w_out"], conv_state, ssm_state


def mamba2_step(cfg: ModelConfig, lp: dict, u: jnp.ndarray,
                conv_state: jnp.ndarray, ssm_state: jnp.ndarray):
    """One position for every row: u (B, D) normalised input, conv_state
    (B, d_conv-1, conv_dim) f32, ssm_state (B, H, P, N) f32 ->
    (out (B, D), conv_state, ssm_state)."""
    with jax.named_scope("ssm.proj"):
        z, xbc, dt = _split_proj(cfg, u @ lp["w_in"])
    with jax.named_scope("ssm.step"):
        window = jnp.concatenate(
            [conv_state, xbc.astype(jnp.float32)[:, None]], axis=1)
        w = lp["conv_w"].astype(jnp.float32)       # (conv_dim, k)
        conv = jnp.einsum("bkc,ck->bc", window, w)
        xbc = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))
        x, bm, cm = _split_xbc(cfg, xbc)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))   # (B, H)
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
        bh, ch = _to_heads(cfg, bm), _to_heads(cfg, cm)             # (B, H, N)
        ssm_state = (jnp.exp(dt * a)[:, :, None, None] * ssm_state
                     + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
        y = (jnp.sum(ssm_state * ch[:, :, None, :], axis=-1)
             + lp["D"].astype(jnp.float32)[:, None] * x)
        y = _gated_norm(cfg, y.reshape(u.shape[0], cfg.mamba_d_inner), z,
                        lp["norm_scale"]).astype(u.dtype)
    with jax.named_scope("ssm.proj"):
        return y @ lp["w_out"], window[:, 1:], ssm_state
