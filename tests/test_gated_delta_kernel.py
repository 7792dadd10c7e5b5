"""The Pallas preparation of the gated delta rule (interpret mode here, as
tests/test_flash_attention.py runs flash): against the ``jnp`` preparation it
replaces and, through the whole rule, against the recurrence position by
position; values and all five gradients.  And the rule that chooses between
the two, from the backend, the dtype and the head size."""

import jax
import jax.numpy as jnp
import pytest

from test_gated_delta import ARGS, CASES, inputs, recurrent_gated_delta_rule
from tpucfn.kernels.gated_delta import gdn_prep
from tpucfn.ops import gated_delta
from tpucfn.ops.gated_delta import chunk_preparation, gated_delta_rule

F32 = jnp.float32
CHUNK = 16


def chunked(q, k, v, g, beta):
    """The rule's own chunking (S a multiple of CHUNK): the arguments of the
    ``jnp`` preparation, and of the kernel, which reads q, k and v unchunked."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep, n = hv // hk, s // CHUNK
    qc, kc = (x.reshape(b, n, CHUNK, hk, dk).transpose(0, 3, 1, 2, 4)
              for x in (q, k))
    vc = v.reshape(b, n, CHUNK, hk, rep, dv).transpose(0, 3, 4, 1, 2, 5)
    gc, bc = (x.reshape(b, n, CHUNK, hk, rep).transpose(0, 3, 4, 1, 2)
              for x in (g, beta))
    cum = jnp.cumsum(gc, axis=-1)
    flat = tuple(x.reshape(b, s, -1) for x in (q, k, v))
    return (qc, kc, vc, cum, bc), flat + (cum, bc)


def jnp_preparation(qc, kc, vc, cum, bc):
    """Chunk-leading, as the kernel writes and the scan slices."""
    return tuple(jnp.moveaxis(x, 3, 0) for x in chunk_preparation(qc, kc, vc, cum, bc))


def to_flat(grads, like):
    """The ``jnp`` path's gradients of qc, kc, vc in the kernel's layout."""
    dq, dk, dv, dcum, dbeta = grads
    b, s = like[0].shape[:2]
    return (dq.transpose(0, 2, 3, 1, 4).reshape(b, s, -1),
            dk.transpose(0, 2, 3, 1, 4).reshape(b, s, -1),
            dv.transpose(0, 3, 4, 1, 2, 5).reshape(b, s, -1), dcum, dbeta)


def worst(got, want):
    """Largest difference over the largest value of the reference."""
    got, want = got.astype(F32), want.astype(F32)
    return float(jnp.max(jnp.abs(got - want))) / (float(jnp.max(jnp.abs(want))) + 1e-30)


def weighed(fn):
    """A scalar of every result, each element with a weight of its own."""
    def loss(*a):
        outs = fn(*a)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum(jnp.sum(jnp.sin(o.astype(F32)) * jnp.cos(
            jnp.arange(o.size, dtype=F32).reshape(o.shape))) for o in outs)
    return loss


@pytest.mark.parametrize("s,g_lo,g_hi", CASES)
@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_the_jnp_preparation_and_the_recurrence(
        monkeypatch, dtype, rep, s, g_lo, g_hi):
    args = inputs(s, s, g_lo, g_hi, hk=2, hv=2 * rep, dtype=dtype)
    low = dtype == jnp.bfloat16

    # the preparation alone, on whole chunks: same roundings, another order
    # of float32 sums; in bfloat16 autodiff also rounds the cotangents of T,
    # k_in and v_in where the kernel keeps float32
    m = max(CHUNK, s // CHUNK * CHUNK)
    whole = tuple(jnp.pad(x, ((0, 0), (0, max(0, m - s))) + ((0, 0),) * (x.ndim - 2)
                          )[:, :m] for x in args)
    jnp_args, kernel_args = chunked(*whole)
    kernel = lambda *a: gdn_prep(*a, interpret=True)  # noqa: E731
    for got, want in zip(jax.jit(kernel)(*kernel_args),
                         jax.jit(jnp_preparation)(*jnp_args)):
        assert got.shape == want.shape and got.dtype == want.dtype == dtype
        assert worst(got, want) <= (2.0 ** -7 if low else 1e-6)
    got = jax.jit(jax.grad(weighed(kernel), argnums=ARGS))(*kernel_args)
    want = to_flat(jax.jit(jax.grad(weighed(jnp_preparation), argnums=ARGS))(
        *jnp_args), kernel_args)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.all(jnp.isfinite(a.astype(F32))))
        assert worst(a, b) <= (3e-2 if low else 1e-4)

    # the whole rule through the kernel, tail and all, against the recurrence
    monkeypatch.setattr(gated_delta, "_kernel_serves", lambda *a: True)
    rule = lambda *a: gated_delta_rule(*a, chunk_size=CHUNK)  # noqa: E731
    args32 = tuple(a.astype(F32) for a in args)
    out, ref = jax.jit(rule)(*args), jax.jit(recurrent_gated_delta_rule)(*args32)
    assert out.shape == ref.shape == (2, s, 2 * rep, 8) and out.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(out.astype(F32))))
    if low:
        assert worst(out, ref) <= 0.05
    else:
        assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a).astype(F32)))  # noqa: E731
    got = jax.jit(jax.grad(loss(rule), argnums=ARGS))(*args)
    want = jax.jit(jax.grad(loss(recurrent_gated_delta_rule), argnums=ARGS))(*args32)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a.astype(F32))))
        if low:
            assert worst(a, b) <= 0.1
        else:    # tests/test_gated_delta.py's, for the jnp path
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
                jnp.max(jnp.abs(b))) + 1e-7


def pallas_calls(fn, *args):
    return [e.params["name"] for e in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _equations(sub)


def abstract(b, s, hk, hv, d, dtype):
    sds = jax.ShapeDtypeStruct
    return (sds((b, s, hk, d), dtype), sds((b, s, hk, d), dtype),
            sds((b, s, hv, d), dtype), sds((b, s, hv), F32), sds((b, s, hv), F32))


# the cell's shapes (qwen3next-ep8-s8192) and what the small models and the
# CPU rehearsals use; the answer to "which backend" is the only thing patched
@pytest.mark.parametrize("backend,dtype,d,chunk,kernel", [
    ("tpu", jnp.bfloat16, 128, 64, True),
    ("cpu", jnp.bfloat16, 128, 64, False),
    ("tpu", jnp.float32, 128, 64, False),
    ("tpu", jnp.bfloat16, 16, 16, False),
    ("tpu", jnp.bfloat16, 128, 20, False),
])
def test_the_path_follows_backend_dtype_and_head_size(
        monkeypatch, backend, dtype, d, chunk, kernel):
    monkeypatch.setattr(gated_delta, "_backend", lambda: backend)
    args = abstract(2, 8192 if d == 128 else 64, 16, 32, d, dtype)
    rule = lambda *a: gated_delta_rule(*a, chunk_size=chunk)  # noqa: E731
    assert pallas_calls(rule, *args) == (["gdn_prep_fwd"] if kernel else [])


def test_unpatched_the_rule_asks_jax_for_the_backend():
    assert gated_delta._backend() == jax.default_backend() == "cpu"
    assert pallas_calls(gated_delta_rule, *abstract(1, 128, 2, 4, 128, jnp.bfloat16)) == []


def test_both_kernels_carry_their_names():
    """The trace's events begin with them (PERF.md section 3)."""
    _, args = chunked(*inputs(0, 32, 0.0, 1.0))
    grad = jax.grad(weighed(lambda *a: gdn_prep(*a, interpret=True)), argnums=ARGS)
    assert pallas_calls(grad, *args) == ["gdn_prep_fwd", "gdn_prep_bwd"]
