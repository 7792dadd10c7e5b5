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
points: :func:`chunk_preparation`, in ``jnp``, whose inverse is a triangular
solve and whose backward pass is autodiff's; and the Pallas kernel pair of
:mod:`tpucfn.kernels.gated_delta`, which keeps a chunk's ``C x C`` tensors in
VMEM, forward and backward.  :func:`gated_delta_rule` picks the kernel from what
it can see (:func:`_kernel_serves`: the backend is a TPU, the compute dtype
bfloat16, the chunk a multiple of the 8 sublanes) and the ``jnp`` form
everywhere else; there is no option.  A head size that is no multiple of the
128 lanes (keys of 96, values of 192) goes to the kernel zero-padded to whole
lane tiles, in the fusion that makes q, k and v: exact for this rule, because a
zero key lane never enters the state and a zero value lane stays zero, and
what it costs is nothing inside the kernel (a (C, 96) tile occupies the lanes
of a (C, 128) one) and the pad and slices around it.  The scan takes each
head's own lanes back, so the state is ``(Dk, Dv)`` on both paths.  The
*scan over chunks*, the cumulative sum that feeds both and ``last`` stay XLA's,
the scan's backward pass autodiff's, each chunk's step rematerialised, so a
step keeps its carry and no more.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular ``a`` (..., C, C), float32, by
    forward substitution (a triangular solve against the identity).  Its
    backward pass is the inverse's own, ``-T^T dT T^T``.  (A product of
    ``log2 C`` factors ``(I - a)(I + a^2)(I + a^4)...``, exact in exact
    arithmetic because ``a^C = 0``, loses everything in float32 where ``beta``
    nears 2 over repeated keys: the powers' entries grow as ``2^k`` times
    binomials and cancel, 0.13 off at C 16 with every entry 1.9.)"""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    return jax.lax.linalg.triangular_solve(a, eye, left_side=True, lower=True,
                                           unit_diagonal=True)


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


def _kernel_serves(dtype, chunk: int) -> bool:
    """The Pallas preparation on a TPU, in bfloat16, for a chunk of whole
    sublanes; a head of any size (:func:`_lanes`)."""
    return _backend() == "tpu" and dtype == jnp.bfloat16 and chunk % 8 == 0


def _lanes(d: int) -> int:
    """A head size rounded up to whole lane tiles: the width the kernel
    reads and writes a head at."""
    return -(-d // LANES) * LANES


def _widen(x, width: int):
    """The last axis zero-padded to ``width``."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def _own(x, width: int):
    """A head's own lanes of a padded one."""
    return x if x.shape[-1] == width else x[..., :width]


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
    the output (B, S, Hv, Dv) in ``v.dtype`` and the float32 state a sequence
    ends with, (B, Hv, Dk, Dv).  ``S`` need not be a multiple of
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
    if _kernel_serves(dtype, c):
        from tpucfn.kernels.gated_delta import gdn_prep

        kw, vw = _lanes(dk), _lanes(dv)
        xs = gdn_prep(_widen(q, kw).reshape(b, n * c, hk * kw),
                      _widen(k, kw).reshape(b, n * c, hk * kw),
                      _widen(v, vw).reshape(b, n * c, hv * vw), cum, bc)
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
        w_i, q_i, k_i = (_own(x, dk) for x in (w_i, q_i, k_i))
        u_i = _own(u_i, dv)
        sd = state.astype(dtype)
        new = (u_i.astype(f32) - jnp.matmul(w_i, sd, preferred_element_type=f32)
               ).astype(dtype)
        out = (jnp.matmul(q_i, sd, preferred_element_type=f32)
               + jnp.matmul(qk_i, new, preferred_element_type=f32))
        state = state * last_i[..., None, None] + jnp.einsum(
            "...ck,...cv->...kv", k_i, new, preferred_element_type=f32)
        return state, out.astype(dtype)

    state, out = jax.lax.scan(step, jnp.zeros((b, hk, rep, dk, dv), f32), xs)
    # (N,B,Hk,R,C,Dv) -> (B,S,Hv,Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * c, hv, dv)[:, :s]
    return out, state.reshape(b, hv, dk, dv)
