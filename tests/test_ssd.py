"""The chunked state-space op against the recurrence position by position:
values and every gradient (through a Mamba-2 mixer's own parameters too),
chunk sizes, lengths that are no multiple of the chunk, decays that underflow."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from tpucfn.models.layers import causal_conv_silu
from tpucfn.ops.ssd import ssd

HIGHEST = jax.lax.Precision.HIGHEST


def chunked(chunk):
    return jax.jit(functools.partial(ssd, chunk_size=chunk))


def recurrent_ssd(x, dt, a, b, c, d=None):
    """The recurrence as the module's head writes it, position by position,
    in float32: ``(y, state)``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    f32 = jnp.float32
    b, c = (jnp.repeat(t.astype(f32), h // g, axis=2) for t in (b, c))

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs            # (B,H,P) (B,H) (B,H,N) (B,H,N)
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, b, c))
    state, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), f32), xs)
    y = jnp.moveaxis(y, 0, 1)
    if d is not None:
        y = y + x.astype(f32) * d[:, None]
    return y.astype(x.dtype), state


def inputs(seed, s, dt_lo, dt_hi, bsz=2, h=4, p=8, g=1, n=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (bsz, s, h, p)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(ks[1], (bsz, s, h), minval=jnp.log(dt_lo),
                                    maxval=jnp.log(dt_hi)))
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    b = jax.random.normal(ks[3], (bsz, s, g, n)).astype(dtype)
    c = jax.random.normal(ks[4], (bsz, s, g, n)).astype(dtype)
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x, dt, a, b, c, d


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, (
        float(jnp.max(jnp.abs(got - want))), scale)


# (S, least and largest dt): whole chunks, a tail, one short chunk; the
# published range 1e-3..1e-1 (decays exp(-0.001)..exp(-1.6) a position), all
# but kept, and steps of 5..40 whose decay exp(dt a) underflows to 0 inside a
# chunk (the summed log-decay reaches -10,000)
CASES = [(64, 1e-3, 1e-1), (37, 1e-3, 1e-1), (5, 1e-3, 1.0), (48, 1e-6, 1e-5),
         (43, 5.0, 40.0)]


@pytest.mark.parametrize("s,dt_lo,dt_hi", CASES)
def test_chunked_matches_the_recurrence_values_and_gradients(s, dt_lo, dt_hi):
    args = inputs(s, s, dt_lo, dt_hi)
    y, state, decay_min = chunked(8)(*args)
    want, want_state = jax.jit(recurrent_ssd)(*args)
    _close(y, want)
    _close(state, want_state)
    assert bool(jnp.isfinite(y).all()) and float(decay_min) < 0

    def loss(fn):
        def f(*a):
            out, st = fn(*a)[:2]
            return jnp.sum(jnp.sin(out)) + jnp.sum(st * st)
        return f

    idx = tuple(range(6))
    got = jax.jit(jax.grad(loss(lambda *a: ssd(*a, chunk_size=8)), idx))(*args)
    ref = jax.jit(jax.grad(loss(recurrent_ssd), idx))(*args)
    for gg, gr in zip(got, ref):
        assert bool(jnp.isfinite(gg).all())
        _close(gg, gr, tol=1e-4)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunk_size_changes_no_value(chunk):
    args = inputs(3, 64, 1e-3, 1e-1, h=8, g=2)
    y, state, _ = chunked(chunk)(*args)
    want, want_state = chunked(32)(*args)[:2]
    _close(y, want)
    _close(state, want_state)


def test_the_log_decay_reported_is_the_worst_chunks():
    x, dt, a, b, c, d = inputs(5, 48, 1e-2, 1.0)
    _, _, decay_min = chunked(16)(x, dt, a, b, c, d)
    sums = (dt * a).reshape(2, 3, 16, 4).sum(axis=2)
    assert float(decay_min) == pytest.approx(float(sums.min()), rel=1e-6)


def test_a_group_serves_consecutive_heads():
    x, dt, a, b, c, d = inputs(7, 32, 1e-3, 1e-1, h=4, g=2)
    y, _, _ = chunked(8)(x, dt, a, b, c, d)
    for grp in range(2):
        sl = slice(2 * grp, 2 * grp + 2)
        alone, _, _ = chunked(8)(x[:, :, sl], dt[:, :, sl], a[sl],
                                 b[:, :, grp:grp + 1], c[:, :, grp:grp + 1], d[sl])
        _close(y[:, :, sl], alone)
    with pytest.raises(ValueError, match="heads over"):
        ssd(x[:, :, :3], dt[:, :, :3], a[:3], b, c, chunk_size=8)


def test_bfloat16_inputs_stay_near_the_float32_recurrence():
    args = inputs(11, 96, 1e-3, 1e-1)
    low = tuple(t.astype(jnp.bfloat16) if i in (0, 3, 4) else t
                for i, t in enumerate(args))
    y, _, _ = chunked(16)(*low)
    assert y.dtype == jnp.bfloat16
    want, _ = jax.jit(recurrent_ssd)(*args)
    err = jnp.abs(y.astype(jnp.float32) - want)
    assert float(jnp.max(err)) < 0.05 * float(jnp.max(jnp.abs(want)))


class _MixerCore(nn.Module):
    """A Mamba-2 mixer's own parameters around either form of the recurrence:
    the convolution with bias, ``A_log``, ``dt_bias`` and ``D``."""

    sequential: bool
    h: int = 4
    p: int = 8
    n: int = 16

    @nn.compact
    def __call__(self, xbc, dt):
        bsz, s, _ = xbc.shape
        h, p, n = self.h, self.p, self.n
        f32 = jnp.float32
        w = self.param("conv", nn.initializers.normal(0.5), (4, xbc.shape[-1]))
        bias = self.param("conv_bias", nn.initializers.normal(0.3), (xbc.shape[-1],))
        a_log = self.param("A_log", lambda k, sh: jnp.log(
            jax.random.uniform(k, sh, f32, 1.0, 16.0)), (h,))
        dt_bias = self.param("dt_bias", nn.initializers.normal(1.0), (h,))
        d = self.param("D", nn.initializers.ones, (h,))
        x, b, c = jnp.split(causal_conv_silu(xbc, w, bias, dtype=f32),
                            [h * p, h * p + n], axis=-1)
        args = (x.reshape(bsz, s, h, p), jax.nn.softplus(dt + dt_bias),
                -jnp.exp(a_log), b.reshape(bsz, s, 1, n), c.reshape(bsz, s, 1, n), d)
        return (recurrent_ssd(*args) if self.sequential
                else ssd(*args, chunk_size=8))[0]


def test_every_parameter_of_a_mixer_gets_the_recurrences_gradient():
    """``A_log``, ``dt_bias``, ``D`` and the convolution's taps and bias, through
    the chunked op (tail padded: 37 positions in chunks of 8) and through the
    recurrence position by position."""
    ks = jax.random.split(jax.random.key(2), 3)
    xbc = jax.random.normal(ks[0], (2, 37, 4 * 8 + 2 * 16))
    dt = jax.random.normal(ks[1], (2, 37, 4)) - 3.0
    params = _MixerCore(False).init(ks[2], xbc, dt)

    def loss(sequential):
        return lambda p: jnp.sum(jnp.sin(
            _MixerCore(sequential).apply(p, xbc, dt)))

    got = jax.jit(jax.grad(loss(False)))(params)
    want = jax.jit(jax.grad(loss(True)))(params)
    assert set(got["params"]) == {"conv", "conv_bias", "A_log", "dt_bias", "D"}
    for name, gr in want["params"].items():
        assert float(jnp.max(jnp.abs(gr))) > 0, name
        _close(got["params"][name], gr, tol=1e-4)
