"""The selective state-space scan of a Mamba-1 mixer (Gu & Dao,
arXiv:2312.00752), in plain JAX with a float32 state: the causal depthwise
convolution before it, the scan over a prefill chunk, and the one-token
update of a decode tick. No Pallas kernel (ROADMAP R4).

Per channel `e` of the inner width E and state index `n` of N, with `A [N, E]`
negative, the recurrence is

    h_t = exp(delta_t A) * h_{t-1} + (delta_t * u_t) B_t      [N, E]
    y_t = sum_n h_t[n] C_t[n] + D * u_t                         [E]

`delta [.., E]` is already `softplus(...)`; `B`, `C [.., N]`. The state is
held `[N, E]` a lane: the TPU tiles an array's last two axes by (8, 128), so
E (5,120 at the published width) lies on the lanes and N (16) on the
sublanes, where `[E, N]` would pad N to 128.

A chunk's positions that are not `keep` (its padding, and a prompt's last
token, which the engine's first decode tick forwards again) take no part in
the state or the convolution's state: their outputs are computed, and the
state is left where the last kept token put it, whatever the padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("mamba_conv")
def conv_chunk(conv_state, x, w, bias, n_keep):
    """Causal depthwise convolution of a chunk: `x [B, T, E]` after the
    `K - 1` inputs before it, `conv_state [B, K - 1, E]`; `w [K, E]`, tap `K -
    1` on the current input. Returns `(out [B, T, E] float32, the state after
    the lane's first `n_keep [B]` inputs)`."""
    k, t = w.shape[0], x.shape[1]
    full = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, K - 1 + T, E]
    wf = w.astype(jnp.float32)
    out = sum(full[:, i: i + t].astype(jnp.float32) * wf[i] for i in range(k)) + bias.astype(jnp.float32)
    take = n_keep[:, None] + jnp.arange(k - 1, dtype=n_keep.dtype)[None, :]  # the K - 1 inputs up to the last kept
    return out, jnp.take_along_axis(full, take[:, :, None], axis=1).astype(conv_state.dtype)


@jax.named_scope("mamba_conv")
def conv_step(conv_state, x, w, bias):
    """One token `x [B, E]` after `conv_state [B, K - 1, E]`: `(out [B, E]
    float32, the state with `x` in and the oldest input out)`."""
    full = jnp.concatenate([conv_state.astype(x.dtype), x[:, None]], axis=1)  # [B, K, E]
    out = jnp.sum(full.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1) + bias.astype(jnp.float32)
    return out, full[:, 1:].astype(conv_state.dtype)


def _update(h, u, delta, b, a):
    """One step of the recurrence for `[B, E]` inputs, `h [B, N, E]`."""
    return jnp.exp(delta[:, None, :] * a) * h + (delta * u)[:, None, :] * b[:, :, None]


def _read(h, c):
    """`sum_n h[n] C[n]`: elementwise in float32 (an einsum over N = 16 would
    be a matmul pass at the default precision)."""
    return jnp.sum(h * c[:, :, None], axis=1)


@jax.named_scope("ssm_scan")
def scan_chunk(state, u, delta, b, c, a, d, keep):
    """A prefill chunk: `state [B, N, E]` float32, `u`, `delta [B, T, E]`,
    `b`, `c [B, T, N]`, `a [N, E]`, `d [E]`, `keep [B, T]` (False where a
    position's step stays out of the state). A `lax.scan` over the chunk's
    tokens, the state in the carry. Returns `(state after the chunk, y [B, T,
    E])`, all float32."""

    def tick(h, xs):
        u_t, dt_t, b_t, c_t, keep_t = xs
        new = _update(h, u_t, dt_t, b_t, a)
        return jnp.where(keep_t[:, None, None], new, h), _read(new, c_t)

    by_time = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    state, ys = jax.lax.scan(tick, state, (by_time(u), by_time(delta), by_time(b), by_time(c), by_time(keep)))
    return state, jnp.moveaxis(ys, 0, 1) + d * u


@jax.named_scope("ssm_step")
def step(state, u, delta, b, c, a, d):
    """A decode tick: `state [B, N, E]`, `u`, `delta [B, E]`, `b`, `c [B,
    N]`. Returns `(state, y [B, E])`, float32."""
    state = _update(state, u, delta, b, a)
    return state, _read(state, c) + d * u

