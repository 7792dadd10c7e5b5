"""The Pallas pairs of the state-space recurrence's chunk-local parts (a
chunk's outputs, and its own sum into the state; interpret mode here, as
tests/test_gated_delta_kernel.py runs its pair): against the ``jnp`` forms they
replace and, through the whole op, against the recurrence position by position;
values and every gradient.  And the rule that chooses between the two, from the
backend, the dtype, the chunk, the state size and the heads' width."""

import functools

import jax
import jax.numpy as jnp
import pytest

from test_gated_delta_kernel import pallas_calls, weighed, worst
from test_ssd import CASES, inputs, recurrent_ssd
from tpucfn.kernels.ssd import heads_a_step, ssd_chunk, ssd_own
from tpucfn.ops import ssd as ssd_op
from tpucfn.ops.ssd import chunk_outputs, ssd

F32 = jnp.float32
CHUNK = 8
ARGS = tuple(range(7))


def chunked(seed, x, dt, a, b, c, d):
    """The op's own chunking (S a multiple of CHUNK) and a state for every
    chunk to start from: the arguments of the ``jnp`` form, and of the kernel,
    which reads x, B and C unchunked."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    nc, r = s // CHUNK, h // g
    dtc = dt.astype(F32).reshape(bsz, nc, CHUNK, g, r)
    cum = jnp.cumsum(dtc * a.reshape(g, r), axis=2)
    before = jax.random.normal(jax.random.key(seed), (nc, bsz, g, r, p, n)
                               ).astype(x.dtype)
    xc = x.reshape(bsz, nc, CHUNK, g, r, p)
    bc, cc = (t.reshape(bsz, nc, CHUNK, g, n) for t in (b, c))
    return ((xc, bc, cc, dtc, cum, before, d),
            (x.reshape(bsz, s, h * p), b.reshape(bsz, s, g * n),
             c.reshape(bsz, s, g * n), dtc, cum, before, d))


def to_flat(grads, like):
    """The ``jnp`` form's gradients of xc, bc, cc in the kernel's layout."""
    return tuple(g.reshape(t.shape) for g, t in zip(grads, like))


@pytest.mark.parametrize("s,dt_lo,dt_hi", CASES)
@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_the_jnp_form_and_the_recurrence(
        monkeypatch, dtype, groups, s, dt_lo, dt_hi):
    args = inputs(s, s, dt_lo, dt_hi, g=groups, dtype=dtype)
    low = dtype == jnp.bfloat16

    # the chunks' outputs alone, on whole chunks: same roundings, another
    # order of float32 sums; in bfloat16 autodiff also rounds the cotangent of
    # m where the kernel keeps float32
    m = max(CHUNK, s // CHUNK * CHUNK)
    whole = tuple(jnp.pad(t, ((0, 0), (0, max(0, m - s))) + ((0, 0),) * (t.ndim - 2)
                          )[:, :m] if t.ndim > 1 else t for t in args)
    jnp_args, kernel_args = chunked(s, *whole)
    kernel = lambda *a: ssd_chunk(*a, interpret=True)  # noqa: E731
    flat = lambda *a: chunk_outputs(*a).reshape(kernel_args[0].shape)  # noqa: E731
    got, want = jax.jit(kernel)(*kernel_args), jax.jit(flat)(*jnp_args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(F32))))
    assert worst(got, want) <= (2.0 ** -7 if low else 1e-6)
    got = jax.jit(jax.grad(weighed(kernel), argnums=ARGS))(*kernel_args)
    want = to_flat(jax.jit(jax.grad(weighed(flat), argnums=ARGS))(*jnp_args),
                   kernel_args)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.all(jnp.isfinite(a.astype(F32))))
        assert worst(a, b) <= (3e-2 if low else 1e-5)

    # each chunk's own sum into the state
    xc, bc = jnp_args[:2]
    to_end = jnp.exp(jnp_args[4][:, :, -1:] - jnp_args[4]) * jnp_args[3]
    p_ = xc.shape[-1]
    own = lambda x, b, t: ssd_own(x, b, t, p_, interpret=True)  # noqa: E731
    einsum = lambda x, b, t: jnp.einsum(  # noqa: E731
        "bcjgrp,bcjgn->cbgrpn", (x.astype(F32) * t[..., None]).astype(dtype), b,
        preferred_element_type=F32)
    got = jax.jit(own)(*kernel_args[:2], to_end)
    want = jax.jit(einsum)(xc, bc, to_end)
    assert got.shape == want.shape and got.dtype == want.dtype == F32
    assert worst(got, want) <= 1e-6
    got = jax.jit(jax.grad(weighed(own), argnums=(0, 1, 2)))(*kernel_args[:2], to_end)
    want = to_flat(jax.jit(jax.grad(weighed(einsum), argnums=(0, 1, 2)))(
        xc, bc, to_end), kernel_args[:2] + (to_end,))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert worst(a, b) <= (3e-2 if low else 1e-5)

    # the whole op through the kernel, tail and all, against the recurrence;
    # in bfloat16 the gradients against the jnp form's, at the same inputs (a
    # bfloat16 y of several hundred under a sine has no float32 neighbour)
    args32 = tuple(t.astype(F32) for t in args)

    def loss(fn):
        def f(*a):
            out, st = fn(*a)
            return jnp.sum(jnp.sin(out.astype(F32))) + jnp.sum(st * st)
        return f

    # a function a path: a trace is kept by the function traced
    jnp_op = lambda *a: ssd(*a, chunk_size=CHUNK)[:2]  # noqa: E731
    jnp_form = jax.jit(jax.grad(loss(jnp_op), argnums=ARGS[:6]))(*args) if low else None
    monkeypatch.setattr(ssd_op, "_kernel_serves", lambda *a: True)
    op = lambda *a: ssd(*a, chunk_size=CHUNK)[:2]  # noqa: E731
    assert pallas_calls(op, *args) == ["ssd_own_fwd", "ssd_chunk_fwd"]
    (y, state), (ref, ref_state) = jax.jit(op)(*args), jax.jit(recurrent_ssd)(*args32)
    assert y.shape == ref.shape and y.dtype == dtype and state.dtype == F32
    assert bool(jnp.all(jnp.isfinite(y.astype(F32))))
    assert worst(y, ref) <= (0.05 if low else 2e-5)
    assert worst(state, ref_state) <= (0.05 if low else 2e-5)
    got = jax.jit(jax.grad(loss(op), argnums=ARGS[:6]))(*args)
    want = jnp_form or jax.jit(jax.grad(loss(recurrent_ssd), argnums=ARGS[:6]))(*args32)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a.astype(F32))))
        # float32: tests/test_ssd.py's, for the jnp form
        assert worst(a, b) <= (3e-2 if low else 1e-4)


def test_a_step_serves_whole_tiles_of_heads():
    """Eight heads a grid step (a sublane tile of their rows) where that is a
    whole number of x's lane tiles, else the whole group."""
    assert heads_a_step(64, 64) == 8 and heads_a_step(32, 128) == 8
    assert heads_a_step(24, 32) == 8 and heads_a_step(4, 64) == 4
    assert heads_a_step(16, 8) == 16 and heads_a_step(12, 64) == 12


def abstract(s, h, p, g, n, dtype):
    sds = jax.ShapeDtypeStruct
    return (sds((1, s, h, p), dtype), sds((1, s, h), F32), sds((h,), F32),
            sds((1, s, g, n), dtype), sds((1, s, g, n), dtype), sds((h,), F32))


# the cell's shapes (granite4h-ssd-s16384) and what the small model, the
# rehearsal and the reference use; the answer to "which backend" is the only
# thing patched
@pytest.mark.parametrize("backend,dtype,chunk,state,heads,width,kernel", [
    ("tpu", jnp.bfloat16, 256, 128, 64, 64, True),
    ("tpu", jnp.bfloat16, 128, 128, 8, 128, True),
    ("cpu", jnp.bfloat16, 256, 128, 64, 64, False),
    ("tpu", jnp.float32, 256, 128, 64, 64, False),
    ("tpu", jnp.bfloat16, 8, 128, 64, 64, False),
    ("tpu", jnp.bfloat16, 256, 16, 64, 64, False),
    ("tpu", jnp.bfloat16, 256, 128, 8, 16, True),
    ("tpu", jnp.bfloat16, 256, 128, 4, 16, False),
    ("tpu", jnp.bfloat16, 256, 128, 8, 48, False),
])
def test_the_path_follows_backend_dtype_chunk_state_and_head_width(
        monkeypatch, backend, dtype, chunk, state, heads, width, kernel):
    monkeypatch.setattr(ssd_op, "_backend", lambda: backend)
    args = abstract(1024, heads, width, 1, state, dtype)
    op = functools.partial(ssd, chunk_size=chunk)
    assert pallas_calls(op, *args) == (
        ["ssd_own_fwd", "ssd_chunk_fwd"] if kernel else [])


def test_unpatched_the_op_asks_jax_for_the_backend():
    assert ssd_op._backend() == jax.default_backend() == "cpu"
    op = functools.partial(ssd, chunk_size=256)
    assert pallas_calls(op, *abstract(512, 64, 64, 1, 128, jnp.bfloat16)) == []


def test_the_kernels_carry_their_names():
    """The trace's events begin with them, and ``ssd_time_share.g4h`` finds
    them by ``ssd_`` (PERF.md section 3)."""
    _, args = chunked(0, *inputs(0, 32, 1e-3, 1e-1))
    grad = jax.grad(weighed(lambda *a: ssd_chunk(*a, interpret=True)), argnums=ARGS)
    assert pallas_calls(grad, *args) == ["ssd_chunk_fwd", "ssd_chunk_bwd"]
    grad = jax.grad(weighed(lambda x, b, t: ssd_own(x, b, t, 8, interpret=True)),
                    argnums=(0, 1, 2))
    assert pallas_calls(grad, *args[:2], args[3]) == ["ssd_own_fwd", "ssd_own_bwd"]
