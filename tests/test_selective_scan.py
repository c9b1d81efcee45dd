"""The prefill scan's Pallas kernel (`selective_scan.scan_chunk`, interpret
mode on the CPU) against the `lax.scan` it replaced
(`selective_scan.reference_scan_chunk`): one parametrised case a shape, over
one, three and sixteen lanes, chunks of 16 and 256 tokens, the tiny and the
published inner widths (E 128 and 5,120) and state sizes (N 4 and 16), a
random starting state, and lanes that keep none, part or all of their chunk
in the state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.ops import selective_scan as ss

# lanes, tokens, inner width E, state size N, how much of each lane's chunk is kept
CASES = [
    (1, 16, 128, 4, "all"),
    (1, 256, 5120, 16, "part"),
    (3, 16, 128, 16, "part"),
    (3, 256, 128, 4, "none"),
    (3, 16, 5120, 4, "none"),
    (16, 16, 5120, 16, "all"),
    (16, 256, 128, 16, "part"),
    (16, 16, 128, 4, "part"),
]


def _inputs(lanes, t, e, n, seed):
    """Operands on the scales a Mamba layer gives them: `delta` a softplus
    around Mamba's initial step sizes, `A` its initial `-(n + 1)` decays."""
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)  # noqa: E731
    a = -jnp.arange(1, n + 1, dtype=jnp.float32)[:, None] * jnp.asarray(r.uniform(0.5, 1.5, (n, e)), jnp.float32)
    return dict(state=f(lanes, n, e), u=f(lanes, t, e), delta=jax.nn.softplus(f(lanes, t, e) - 3.0),
                b=f(lanes, t, n), c=f(lanes, t, n), a=a, d=f(e))


def _n_keep(lanes, t, kept, seed):
    if kept == "none":
        return np.zeros(lanes, np.int32)
    if kept == "all":
        return np.full(lanes, t, np.int32)
    return np.random.default_rng(seed).integers(1, t, lanes).astype(np.int32)  # part of the chunk, a length a lane


_kernel = jax.jit(ss.scan_chunk)
_reference = jax.jit(ss.reference_scan_chunk)


@pytest.mark.parametrize("lanes,t,e,n,kept", CASES)
def test_the_kernel_matches_the_lax_scan_and_leaves_unkept_positions_out(lanes, t, e, n, kept):
    """The state after the chunk and every position's `y` agree with the
    reference to 1e-5 of the largest magnitude (a sum over N of terms of
    either sign can cancel, so an element near zero has no relative error to
    speak of). A position that is not kept leaves the state bit-equal to what
    the last kept token left: other inputs there change neither the state nor
    a kept position's `y`, and a lane that keeps nothing hands its state back
    as it came."""
    seed = lanes * 1000 + t + e + n
    x = _inputs(lanes, t, e, n, seed)
    keep = jnp.arange(t)[None, :] < jnp.asarray(_n_keep(lanes, t, kept, seed))[:, None]
    args = (x["state"], x["u"], x["delta"], x["b"], x["c"], x["a"], x["d"], keep)
    state, y = (np.asarray(z) for z in _kernel(*args))
    want_state, want_y = (np.asarray(z) for z in _reference(*args))
    assert state.shape == want_state.shape and y.shape == want_y.shape
    for got, want in ((state, want_state), (y, want_y)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())
    if kept == "none":
        assert np.array_equal(state, np.asarray(x["state"]))
    if kept == "part":
        other = _inputs(lanes, t, e, n, seed + 1)
        noisy = {k: jnp.where(keep[..., None], x[k], other[k]) for k in ("u", "delta", "b", "c")}
        state2, y2 = (np.asarray(z) for z in _kernel(x["state"], noisy["u"], noisy["delta"], noisy["b"], noisy["c"],
                                                     x["a"], x["d"], keep))
        assert np.array_equal(state2, state)
        kept_rows = np.asarray(keep)
        assert np.array_equal(y2[kept_rows], y[kept_rows])
