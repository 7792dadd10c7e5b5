"""The gated delta rule: the recurrence of a Gated DeltaNet layer.

Per value head, with a state ``S`` of (key dim, value dim) that starts at
zero, for each position ``t``::

    S   <- exp(g_t) * S                      # decay, g_t <= 0
    u   =  beta_t * (v_t - S^T k_t)          # what the state does not hold yet
    S   <- S + k_t u^T
    o_t =  S^T q_t

``q`` and ``k`` arrive normalised (and ``q`` scaled) by the caller; a key head
serves ``Hv // Hk`` consecutive value heads.

:func:`gated_delta_rule` is the chunked form the models train through (the
tests hold it to that loop, position by position).  Within a chunk of ``C``
positions the updates ``u`` solve a unit lower-triangular system,

    (I + strict_tril(diag(beta) (K K^T * decay))) U = diag(beta) (V - decayed K S),

whose inverse ``T`` is made for all chunks at once in float32; across chunks a
``lax.scan`` carries ``S``.  Cumulative log-decays are float32, every decay
factor used is ``exp`` of a non-positive number, and the large products run in
the inputs' dtype with float32 accumulation.

Two parts, two owners.  The *chunk-local preparation* (decay mask, ``K K^T``,
``A``, ``T``, ``W = T K_in``, ``U = T V_in``, ``Q K^T * decay``, ``q_in``,
``k_out``: everything a chunk can know without the state) has two
implementations of one arithmetic, rounded to the compute dtype at the same
points: :func:`chunk_preparation`, in ``jnp``, whose inverse is a product of
``log2 C`` matrix factors (the strict triangle is nilpotent) and whose backward
pass is autodiff's; and the Pallas kernel pair of
:mod:`tpucfn.kernels.gated_delta`, which keeps a chunk's ``C x C`` tensors in
VMEM, forward and backward.  :func:`gated_delta_rule` picks the kernel from what
it can see (:func:`_kernel_serves`: the backend is a TPU, the compute dtype
bfloat16, both head sizes multiples of the 128 lanes, the chunk a multiple of
the 8 sublanes) and the ``jnp`` form everywhere else; there is no option.  The
*scan over chunks*, the cumulative sum that feeds both and ``last`` stay XLA's,
the scan's backward pass autodiff's, each chunk's step rematerialised, so a
step keeps its carry and no more.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular ``a`` (..., C, C), float32:
    sum_k (-a)^k = (I - a)(I + a^2)(I + a^4)..., exact because a^C = 0.  Its
    backward pass is the inverse's own, ``-T^T dT T^T``, so that the factors
    are not kept."""
    c = a.shape[-1]
    p = -a
    t = jnp.eye(c, dtype=a.dtype) + p
    power = 1
    while 2 * power < c:
        p = jnp.matmul(p, p, precision=HIGHEST)
        t = t + jnp.matmul(t, p, precision=HIGHEST)
        power *= 2
    return t


def _unit_lower_inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, dt, precision=HIGHEST), tt,
                        precision=HIGHEST),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _backend() -> str:
    return jax.default_backend()


def _kernel_serves(dtype, dk: int, dv: int, chunk: int) -> bool:
    """The Pallas preparation where its tiles are whole: on a TPU, in
    bfloat16, head sizes that fill the 128 lanes, a chunk of whole sublanes."""
    return (_backend() == "tpu" and dtype == jnp.bfloat16
            and dk % 128 == 0 and dv % 128 == 0 and chunk % 8 == 0)


def chunk_preparation(qc, kc, vc, cum, bc):
    """What the scan over chunks is fed, less ``last``, from the chunked
    inputs: qc, kc (B,Hk,N,C,Dk); vc (B,Hk,R,N,C,Dv); the log-decay summed
    inside each chunk and beta, (B,Hk,R,N,C) float32.  Returns ``w, u, qk,
    q_in, k_out``, each (B,Hk,R,N,C,·) in ``vc.dtype``.  The ``jnp`` form:
    what runs wherever the kernel does not, and what the kernel is held to."""
    c = qc.shape[3]
    dtype, f32 = vc.dtype, jnp.float32
    lower = jnp.tril(jnp.ones((c, c), bool))
    diff = cum[..., :, None] - cum[..., None, :]        # (…,i,j): from j to i
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    def per_key_head(x, y):
        return jnp.einsum("bhncd,bhnkd->bhnck", x, y,
                          preferred_element_type=f32)[:, :, None]

    a = bc[..., None] * per_key_head(kc, kc) * jnp.tril(decay, -1)
    t = _unit_lower_inverse(a).astype(dtype)            # (B,Hk,R,N,C,C)
    kv_heads = kc[:, :, None].astype(f32)
    k_in = (kv_heads * (bc * jnp.exp(cum))[..., None]).astype(dtype)
    w = jnp.matmul(t, k_in, preferred_element_type=f32).astype(dtype)
    u = jnp.matmul(t, (vc.astype(f32) * bc[..., None]).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    qk = (per_key_head(qc, kc) * decay).astype(dtype)
    q_in = (qc[:, :, None].astype(f32) * jnp.exp(cum)[..., None]).astype(dtype)
    k_out = (kv_heads * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)
    return w, u, qk, q_in, k_out


def gated_delta_rule(q, k, v, g, beta, *, chunk_size: int = 64):
    """q, k: (B, S, Hk, Dk); v: (B, S, Hv, Dv); g, beta: (B, S, Hv).  Returns
    (B, S, Hv, Dv) in ``v.dtype``.  ``S`` need not be a multiple of
    ``chunk_size``: the tail is padded with positions that change nothing."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if hv % hk:
        raise ValueError(f"{hv} value heads over {hk} key heads")
    rep, c = hv // hk, chunk_size
    dtype, f32 = v.dtype, jnp.float32
    pad = (-s) % c
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    n = (s + pad) // c

    # value heads (B,Hk,R,N,C): the log-decay inside the chunk, and beta
    gc, bc = (x.astype(f32).reshape(b, n, c, hk, rep).transpose(0, 3, 4, 1, 2)
              for x in (g, beta))
    cum = jnp.cumsum(gc, axis=-1)
    if _kernel_serves(dtype, dk, dv, c):
        from tpucfn.kernels.gated_delta import gdn_prep

        xs = gdn_prep(q.reshape(b, n * c, hk * dk), k.reshape(b, n * c, hk * dk),
                      v.reshape(b, n * c, hv * dv), cum, bc)
    else:
        # chunked, heads leading: key heads (B,Hk,N,C,·), value heads (B,Hk,R,N,C,·)
        qc, kc = (x.reshape(b, n, c, hk, dk).transpose(0, 3, 1, 2, 4) for x in (q, k))
        vc = v.reshape(b, n, c, hk, rep, dv).transpose(0, 3, 4, 1, 2, 5)
        xs = tuple(jnp.moveaxis(x, 3, 0)
                   for x in chunk_preparation(qc, kc, vc, cum, bc))
    xs += (jnp.moveaxis(jnp.exp(cum[..., -1]), 3, 0),)   # last: (N,B,Hk,R)

    @jax.checkpoint
    def step(state, xs):
        w_i, u_i, qk_i, q_i, k_i, last_i = xs
        sd = state.astype(dtype)
        new = (u_i.astype(f32) - jnp.matmul(w_i, sd, preferred_element_type=f32)
               ).astype(dtype)
        out = (jnp.matmul(q_i, sd, preferred_element_type=f32)
               + jnp.matmul(qk_i, new, preferred_element_type=f32))
        state = state * last_i[..., None, None] + jnp.einsum(
            "...ck,...cv->...kv", k_i, new, preferred_element_type=f32)
        return state, out.astype(dtype)

    _, out = jax.lax.scan(step, jnp.zeros((b, hk, rep, dk, dv), f32), xs)
    # (N,B,Hk,R,C,Dv) -> (B,S,Hv,Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * c, hv, dv)
    return out[:, :s]
