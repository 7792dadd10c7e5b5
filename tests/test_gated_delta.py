"""The chunked gated delta rule against the recurrence position by position:
values and gradients, sequence lengths that are no multiple of the chunk,
decays near 0 and near 1, and beta over (0, 2), where the transition reflects
(a published ``allow_neg_eigval``)."""

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


def reflecting(seed, s, repeated, dtype=jnp.float32, **sizes):
    """Inputs whose beta lies over (0, 2): uniform there, or near 2 with each
    key repeated over four positions in a row, where the chunk's triangular
    system has off-diagonal entries of nearly 2 (``beta k_i . k_j`` at
    ``k_i = k_j``) and its inverse the largest entries it can have."""
    q, k, v, g, _ = inputs(seed, s, 0.0, 0.2, **sizes)
    ks = jax.random.split(jax.random.key(seed + 1), 2)
    if repeated:
        k = jnp.repeat(k[:, ::4], 4, axis=1)[:, :s]
        beta = jax.random.uniform(ks[0], g.shape, minval=1.9, maxval=2.0)
    else:
        beta = jax.random.uniform(ks[1], g.shape, minval=0.0, maxval=2.0)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


# (S, least and largest -g): whole chunks, a tail, one short chunk; decay
# exp(g) near 1 (1e-4: the state is all but kept), mixed, and near 0 (40: the
# state is forgotten at once, exp underflows to 0 inside a chunk)
CASES = [(64, 0.0, 0.1), (100, 0.0, 0.1), (9, 0.0, 3.0), (64, 1e-4, 1e-3),
         (75, 5.0, 40.0)]


@pytest.mark.parametrize("s,g_lo,g_hi", CASES)
def test_chunked_matches_the_recurrence_values_and_gradients(s, g_lo, g_hi):
    args = inputs(s, s, g_lo, g_hi)
    chunked = lambda *a: gated_delta_rule(*a, chunk_size=16)[0]  # noqa: E731
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
    out, _ = gated_delta_rule(*args, chunk_size=chunk)
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6


def test_bfloat16_inputs_stay_near_the_float32_recurrence():
    """The training dtype: products in bfloat16 with float32 sums, decays and
    the triangular inverse in float32.  8 bits of mantissa: 2^-8 a product,
    a few products deep."""
    args32 = inputs(4, 128, 0.0, 1.0)
    args16 = tuple(a.astype(jnp.bfloat16) for a in args32[:3]) + args32[3:]
    out, _ = gated_delta_rule(*args16, chunk_size=64)
    assert out.dtype == jnp.bfloat16
    ref = recurrent_gated_delta_rule(*args32)
    err = jnp.abs(out.astype(jnp.float32) - ref)
    assert float(jnp.max(err)) <= 0.05 * float(jnp.max(jnp.abs(ref)))


def test_a_key_head_serves_consecutive_value_heads():
    q, k, v, g, beta = inputs(5, 32, 0.0, 1.0)
    out, state = gated_delta_rule(q, k, v, g, beta, chunk_size=16)
    # value heads 2 and 3 belong to key head 1: the same as a call of their own
    alone, alone_state = gated_delta_rule(
        q[:, :, 1:], k[:, :, 1:], v[:, :, 2:], g[..., 2:], beta[..., 2:], chunk_size=16)
    assert float(jnp.max(jnp.abs(out[:, :, 2:] - alone))) <= 1e-6
    # and the state each sequence ends with, a value head's own
    assert state.shape == (2, 4, 16, 8)
    assert float(jnp.max(jnp.abs(state[:, 2:] - alone_state))) <= 1e-6
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


@pytest.mark.parametrize("repeated", [False, True], ids=["uniform", "near2_repeated"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_beta_over_0_2_matches_the_recurrence(dtype, repeated):
    """Reflections: beta in (0, 2) gives the transition I - beta k k^T an
    eigenvalue in (-1, 1) along k.  The chunked form is held to the loop
    position by position, values and all five gradients, with the inverse of
    the chunk's triangular system rounded where it always is."""
    args = reflecting(7, 48, repeated, dtype)
    args32 = tuple(a.astype(jnp.float32) for a in args)
    chunked = lambda *a: gated_delta_rule(*a, chunk_size=16)[0]  # noqa: E731
    out = jax.jit(chunked)(*args)
    ref = jax.jit(recurrent_gated_delta_rule)(*args32)
    assert out.dtype == dtype and bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))  # noqa: E731
    got = jax.jit(jax.grad(loss(chunked), argnums=ARGS))(*args)
    want = jax.jit(jax.grad(loss(recurrent_gated_delta_rule), argnums=ARGS))(*args32)
    if dtype == jnp.float32:
        # float32 both ways, the sums in another order: as at beta in (0, 1)
        assert err <= 2e-6 * max(1.0, scale)
        for a, b in zip(got, want):
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-7
    else:
        # bfloat16 products, 8 bits of mantissa a few products deep: the bound
        # of the bfloat16 test above, beside a float32 loop fed the same inputs
        assert err <= 0.05 * scale
        for a, b in zip(got, want):
            a = a.astype(jnp.float32)
            assert bool(jnp.all(jnp.isfinite(a)))
            assert float(jnp.max(jnp.abs(a - b))) <= 0.1 * float(jnp.max(jnp.abs(b)))
