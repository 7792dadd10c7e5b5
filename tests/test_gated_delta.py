"""The chunked gated delta rule against the recurrence position by position:
values and gradients, sequence lengths that are no multiple of the chunk,
decays near 0 and near 1."""

import jax
import jax.numpy as jnp
import pytest

from tpucfn.ops.gated_delta import _unit_lower_inverse, gated_delta_rule

HIGHEST = jax.lax.Precision.HIGHEST

ARGS = (0, 1, 2, 3, 4)


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """The recurrence as the module's head writes it, position by position,
    in float32."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    f32 = jnp.float32
    rep = hv // hk
    q, k = (jnp.repeat(x.astype(f32), rep, axis=2) for x in (q, k))

    def step(state, xs):
        qt, kt, vt, gt, bt = xs                       # (B,Hv,·)
        state = state * jnp.exp(gt)[..., None, None]
        held = jnp.einsum("bhkv,bhk->bhv", state, kt, precision=HIGHEST)
        u = bt[..., None] * (vt - held)
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), f32), xs)
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


def inputs(seed, s, g_lo, g_hi, b=2, hk=2, hv=4, dk=16, dv=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    g = -jax.random.uniform(ks[3], (b, s, hv), minval=g_lo, maxval=g_hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


# (S, least and largest -g): whole chunks, a tail, one short chunk; decay
# exp(g) near 1 (1e-4: the state is all but kept), mixed, and near 0 (40: the
# state is forgotten at once, exp underflows to 0 inside a chunk)
CASES = [(64, 0.0, 0.1), (100, 0.0, 0.1), (9, 0.0, 3.0), (64, 1e-4, 1e-3),
         (75, 5.0, 40.0)]


@pytest.mark.parametrize("s,g_lo,g_hi", CASES)
def test_chunked_matches_the_recurrence_values_and_gradients(s, g_lo, g_hi):
    args = inputs(s, s, g_lo, g_hi)
    chunked = lambda *a: gated_delta_rule(*a, chunk_size=16)  # noqa: E731
    out, ref = jax.jit(chunked)(*args), jax.jit(recurrent_gated_delta_rule)(*args)
    assert out.shape == ref.shape == (2, s, 4, 8)
    assert bool(jnp.all(jnp.isfinite(out)))
    # float32 both ways; the chunked form sums in another order
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))  # noqa: E731
    got = jax.jit(jax.grad(loss(chunked), argnums=ARGS))(*args)
    want = jax.jit(jax.grad(loss(recurrent_gated_delta_rule), argnums=ARGS))(*args)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-7


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_the_chunk_size_changes_no_value(chunk):
    args = inputs(3, 96, 0.0, 2.0)
    ref = recurrent_gated_delta_rule(*args)
    out = gated_delta_rule(*args, chunk_size=chunk)
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6


def test_bfloat16_inputs_stay_near_the_float32_recurrence():
    """The training dtype: products in bfloat16 with float32 sums, decays and
    the triangular inverse in float32.  8 bits of mantissa: 2^-8 a product,
    a few products deep."""
    args32 = inputs(4, 128, 0.0, 1.0)
    args16 = tuple(a.astype(jnp.bfloat16) for a in args32[:3]) + args32[3:]
    out = gated_delta_rule(*args16, chunk_size=64)
    assert out.dtype == jnp.bfloat16
    ref = recurrent_gated_delta_rule(*args32)
    err = jnp.abs(out.astype(jnp.float32) - ref)
    assert float(jnp.max(err)) <= 0.05 * float(jnp.max(jnp.abs(ref)))


def test_a_key_head_serves_consecutive_value_heads():
    q, k, v, g, beta = inputs(5, 32, 0.0, 1.0)
    out = gated_delta_rule(q, k, v, g, beta, chunk_size=16)
    # value heads 2 and 3 belong to key head 1: the same as a call of their own
    alone = gated_delta_rule(q[:, :, 1:], k[:, :, 1:], v[:, :, 2:], g[..., 2:],
                             beta[..., 2:], chunk_size=16)
    assert float(jnp.max(jnp.abs(out[:, :, 2:] - alone))) <= 1e-6
    with pytest.raises(ValueError):
        gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


def test_the_triangular_inverse_and_its_own_backward_pass():
    a = jnp.tril(jax.random.normal(jax.random.key(0), (3, 16, 16)), -1)
    t = _unit_lower_inverse(a)
    eye = jnp.eye(16)
    assert float(jnp.max(jnp.abs(t @ (eye + a) - eye))) <= 1e-4
    f = lambda fn: lambda a: jnp.sum(jnp.cos(fn(a)))  # noqa: E731
    got = jax.grad(f(_unit_lower_inverse))(a)
    want = jax.grad(f(lambda a: jnp.linalg.inv(eye + a)))(a)
    # the strict triangle is what the layer builds; the rest is masked away
    mask = jnp.tril(jnp.ones((16, 16)), -1)
    assert float(jnp.max(jnp.abs((got - want) * mask))) <= 1e-3 * float(
        jnp.max(jnp.abs(want)))
