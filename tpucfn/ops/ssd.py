"""The state-space recurrence of a Mamba-2 layer, in its chunked ("state-space
dual") form.

Per head, with a state ``H`` of (head dim, state dim) that starts at zero, a
scalar decay rate ``a < 0`` and a step ``dt_t >= 0``, for each position ``t``::

    H   <- exp(dt_t * a) * H + dt_t * x_t B_t^T
    y_t =  H C_t + d * x_t

``B`` and ``C`` belong to a *group* of ``H // G`` consecutive heads (one group
for all heads where ``G`` is 1).  The state's update is a diagonal decay and a
rank-one sum, not the delta rule's rank-one correction (``ops/gated_delta.py``
inverts a triangular system a chunk; nothing is inverted here).

:func:`ssd` is the form the models train through (the tests hold it to that
loop, position by position).  With ``l`` the log-decay summed inside a chunk of
``L`` positions (``l_i = sum_{t <= i} dt_t a``), a chunk's output is

    y_i = sum_{j <= i} exp(l_i - l_j) dt_j (C_i . B_j) x_j      # chunk-local
        + exp(l_i) H_in C_i                                     # from the state

and the state it hands on ``exp(l_L) H_in + sum_j exp(l_L - l_j) dt_j x_j
B_j^T``.  Each chunk's own sum is made for all chunks at once and a ``lax.scan``
over chunks carries ``H``; then every chunk's output is made from the state it
starts from.  Log-decays and the state are float32, every decay factor is
``exp`` of a sum or a difference that is never positive (so a step whose decay
underflows gives 0, not ``inf * 0``), a position's factor on itself is the
constant 1 and not ``exp(l_i - l_i)`` (whose gradient, two equal terms of
opposite sign, would be rounded at the size of the undecayed term and lose the
decayed ones), and the large products run in the inputs' dtype with float32
accumulation.

Two owners.  What a chunk makes on its own has two implementations of one
arithmetic, rounded at the same points.  In ``jnp`` (the einsum for each
chunk's own sum into the state, and :func:`chunk_outputs` for the chunks'
outputs once the states they start from are known: a group's ``C_i . B_j``,
each head's ``(L, L)`` decays, their product with ``dt_j`` and ``x``, the
state's part, ``d x``), made for every chunk and head at once, the backward
pass autodiff's; and the Pallas kernels of :mod:`tpucfn.kernels.ssd`
(``ssd_own``, ``ssd_chunk``), which keep a chunk's tensors in VMEM, forward and
backward.  :func:`ssd` picks the kernels from what it can see
(:func:`_kernel_serves`: the backend is a TPU, the compute dtype bfloat16, the
chunk and the state size multiples of the 128 lanes, the heads whole lane
tiles) and the ``jnp`` form everywhere else; there is no option.  The
cumulative sums and the scan over chunks stay XLA's on both paths, their
backward pass autodiff's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _backend() -> str:
    return jax.default_backend()


def _kernel_serves(dtype, chunk: int, state: int, heads: int, width: int) -> bool:
    """The Pallas kernels where their tiles are whole: on a TPU, in bfloat16,
    the chunk and the state size multiples of the 128 lanes, and a group's
    ``heads`` heads of ``width`` channels whole lane tiles of x (a head a
    multiple of 128 wide, or 128 // width heads to a tile)."""
    tile = max(1, 128 // width)
    return (_backend() == "tpu" and dtype == jnp.bfloat16
            and chunk % 128 == 0 and state % 128 == 0
            and (tile * width) % 128 == 0 and heads % tile == 0)


def ssd(x, dt, a, b, c, d=None, *, chunk_size: int = 256):
    """x: (B, S, H, P); dt: (B, S, H), the step after its softplus; a: (H,),
    negative; b, c: (B, S, G, N) with ``H % G == 0``; d: (H,) or None.

    Returns ``(y, state, log_decay_min)``: y (B, S, H, P) in ``x.dtype``, the
    state the sequences end with (B, H, P, N) float32, and the most negative
    log-decay summed inside one chunk (a scalar: where ``exp`` of it leaves
    float32's range, a form that factors ``exp(l_i) * exp(-l_j)`` would be
    wrong; this one is not).  ``S`` need not be a multiple of ``chunk_size``:
    the tail is padded with positions of ``dt = 0``, which leave the state as
    it is."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads over {g} groups")
    r, L = h // g, chunk_size
    dtype, f32 = x.dtype, jnp.float32
    pad = (-s) % L
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // L

    # chunked; heads as (group, head in group) so that B and C serve a group
    xc = x.reshape(bsz, nc, L, g, r, p)
    bc, cc = (t.reshape(bsz, nc, L, g, n) for t in (b, c))
    dtc = dt.astype(f32).reshape(bsz, nc, L, g, r)
    step = dtc * a.astype(f32).reshape(g, r)                       # dt_t a <= 0
    cum = jnp.cumsum(step, axis=2)                                 # l_i
    # l_L - l_i summed on its own (sum_{t > i} dt_t a), not subtracted: the
    # last position's exp(0) would else put its whole gradient on l_L twice,
    # with opposite signs, and drown the decayed positions' in the rounding
    after = jax.lax.cumsum(jnp.pad(step[:, :, 1:], ((0, 0), (0, 0), (0, 1),
                                                    (0, 0), (0, 0))),
                           axis=2, reverse=True)
    kernels = b.dtype == c.dtype == dtype and _kernel_serves(dtype, L, n, r, p)
    if kernels:
        from tpucfn.kernels.ssd import ssd_chunk, ssd_own

        flat = tuple(t.reshape(bsz, nc * L, -1) for t in (x, b, c))

    # what each chunk adds to the state, and what it leaves of the one it got
    to_end = jnp.exp(after) * dtc                                 # (B,Nc,L,G,R)
    if kernels:
        own = ssd_own(flat[0], flat[1], to_end, p)
    else:
        own = jnp.einsum("bcjgrp,bcjgn->cbgrpn",
                         (xc.astype(f32) * to_end[..., None]).astype(dtype), bc,
                         preferred_element_type=f32)
    kept = jnp.moveaxis(jnp.exp(cum[:, :, -1]), 1, 0)             # (Nc,B,G,R)

    def step(state, xs):
        own_i, kept_i = xs
        return state * kept_i[..., None, None] + own_i, state.astype(dtype)

    # before: the state each chunk starts from, as the products take it
    state, before = jax.lax.scan(step, jnp.zeros((bsz, g, r, p, n), f32),
                                 (own, kept))
    if kernels:
        y = ssd_chunk(*flat, dtc, cum, before,
                      jnp.zeros((h,), f32) if d is None else d)
    else:
        y = chunk_outputs(xc, bc, cc, dtc, cum, before, d)
    y = y.reshape(bsz, nc * L, h, p)[:, :s]
    return y, state.reshape(bsz, h, p, n), jnp.min(cum[:, :, -1])


def chunk_outputs(xc, bc, cc, dtc, cum, before, d):
    """Every chunk's outputs from what a chunk can see: xc (B,Nc,L,G,R,P); bc,
    cc (B,Nc,L,G,N); the steps and the log-decay summed inside each chunk,
    (B,Nc,L,G,R) float32; ``before`` (Nc,B,G,R,P,N), the state each chunk
    starts from, in ``xc.dtype``; d (H,) or None.  Returns (B,Nc,L,G,R,P) in
    ``xc.dtype``.  The ``jnp`` form: what runs wherever the kernel does not,
    and what the kernel is held to."""
    L, g, r = xc.shape[2:5]
    dtype, f32 = xc.dtype, jnp.float32
    heads_first = lambda t: jnp.moveaxis(t, 2, -1)  # noqa: E731  (B,Nc,G,R,L)
    cum_h, dt_h = heads_first(cum), heads_first(dtc)

    # chunk-local: (C_i . B_j) a group, times each head's decay from j to i
    # (the diagonal is 1 by itself, as l_L - l_i is summed by itself: no
    # l_i - l_i)
    below = jnp.tril(jnp.ones((L, L), bool), -1)
    diff = cum_h[..., :, None] - cum_h[..., None, :]              # (…,i,j)
    decay = (jnp.where(below, jnp.exp(jnp.where(below, diff, 0.0)), 0.0)
             + jnp.eye(L, dtype=f32))
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc, preferred_element_type=f32)
    m = (cb[:, :, :, None] * decay * dt_h[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xc, preferred_element_type=f32)
    # the state's part
    y = y + jnp.einsum("bcign,cbgrpn->bcigrp", cc, before,
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    if d is not None:
        y = y + xc.astype(f32) * d.astype(f32).reshape(g, r, 1)
    return y.astype(dtype)
