"""The gated delta rule's chunk-local preparation as a Pallas kernel pair.

What :func:`tpucfn.ops.gated_delta.chunk_preparation` computes with ``jnp``
(one float32 ``(C, C)`` tensor after another through HBM, for every value head
of every chunk), computed with a chunk's working set in VMEM: the decay mask,
``K K^T``, the strictly lower-triangular ``A``, its unit-triangular inverse
``T``, ``W = T K_in``, ``U = T V_in``, ``Q K^T * decay`` and the decayed
``q_in`` and ``k_out``.  One grid step serves ``CHUNKS`` chunks of one key head
and the ``R`` value heads it serves: ``K K^T`` and ``Q K^T`` are made once a
key head.  ``q``, ``k`` and ``v`` are read where the layer left them,
``(B, S, heads * width)``, a head's lanes picked by the block's index, and the
results are written chunk-leading, ``(N, B, Hk, R, C, ·)``: what the scan over
chunks slices, so no transpose stands between the layer, the kernel and the
scan.

The arithmetic is the ``jnp`` path's: cumulative log-decays, mask and ``A`` in
float32, every decay factor ``exp`` of a non-positive number, the inverse in
float32, ``T``, ``k_in``, ``v_in``, ``qk``, ``q_in`` and ``k_out`` rounded to
the compute dtype where they are rounded there, the large products in the
compute dtype with float32 accumulation.  The inverse is made in two halves:
the two diagonal blocks of every value head's ``A`` by forward substitution on
the vector unit (exact float32 multiply-adds, all the blocks side by side in
the lanes), the block below them as ``-T22 (A21 T11)`` in two ``HIGHEST``
products.  (Read on the chip, PERF.md PR 28: the ten ``HIGHEST`` products of the
doubling form cost a forward kernel 10 ms, substitution over all 64 rows 4.5,
this 3.1; a substitution step is bound by the permutation of the lanes that
spreads a column's entries over a tile.)  The cumulative sum over a chunk and
``last`` stay outside: they are ``(B, Hv, S)`` floats.

:func:`gdn_prep` carries a ``jax.custom_vjp``: the backward kernel takes the
inputs and the five cotangents, rebuilds ``decay``, ``A`` and ``T`` in VMEM and
returns the cotangents of ``q, k, v, cum, beta``; the chain is autodiff's
(``dT = dW K_in^T + dU V_in^T``, ``dA = -T^T dT T^T`` in ``HIGHEST`` products,
then mask, ``beta``, ``K K^T`` and the decays), its sums kept in float32 until
a result is written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32
CHUNKS = 8        # chunks a grid step: the float32 sublane tile of (N, C)

NT = (((1,), (1,)), ((), ()))   # x @ y^T
TN = (((0,), (0,)), ((), ()))   # x^T @ y


def _dot(x, y, dims=(((1,), (0,)), ((), ())), precision=None):
    return lax.dot_general(x, y, dims, precision=precision,
                           preferred_element_type=F32)


def _masks(c):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row == col, row >= col, row > col


def _to_col(row, eye):
    """(1, C) -> (C, 1), exactly: one term a sum."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _diagonal(x, eye):
    """The diagonal of (C, C), or a column (C, 1), as a row (1, C)."""
    return jnp.sum(jnp.where(eye, x, 0.0), axis=0, keepdims=True)


@jax.jit
def _take_out(t, a, row, lanes):
    """A tile of the rows below less their entries of a column (``lanes``: for
    every lane, the lane of its block that holds the column) times the row
    that is final.  Jitted: a substitution calls it a hundred times at one
    shape, and only the first call is traced."""
    return t - jnp.take_along_axis(a, lanes, axis=1) * row


def _substitute(a, width):
    """(I + block)^-1 for every strictly lower-triangular float32 block of
    ``a`` (width, blocks * width), the blocks side by side in the lanes, by
    forward substitution: row ``i`` of a block's inverse is final once rows
    ``0..i-1`` have been taken out of it, so step ``i`` subtracts
    ``block[:, i] row_i`` from the rows below.  One permutation of the lanes
    a tile a step spreads every block's column ``i`` over its lanes."""
    lanes, tiles = a.shape[1], width // 8
    lane = lax.broadcasted_iota(jnp.int32, (8, lanes), 1)
    first = (lane // width) * width                  # a block's first lane
    unit = (lax.broadcasted_iota(jnp.int32, (width, lanes), 0)
            == lax.broadcasted_iota(jnp.int32, (width, lanes), 1) % width)
    t = [unit[8 * b:8 * b + 8].astype(F32) for b in range(tiles)]
    a = [a[8 * b:8 * b + 8] for b in range(tiles)]
    for i in range(width - 1):
        row, column = t[i // 8][i % 8:i % 8 + 1], first + i
        for b in range(i // 8, tiles):               # block[k, i] = 0 for k <= i
            t[b] = _take_out(t[b], a[b], row, column)
    return t[0] if tiles == 1 else jnp.concatenate(t, axis=0)


def _unit_lower_inverses(a_s):
    """(I + a)^-1 for each strictly lower-triangular float32 (C, C) of the
    list, the matrices side by side in the lanes.  The two diagonal halves of
    each by substitution, all at once; the block below them is
    ``-T22 (A21 T11)``, two ``HIGHEST`` products whose second operand holds a
    matrix's block where its lanes meet its rows, so that one product serves
    every matrix."""
    c, n = a_s[0].shape[0], len(a_s)
    a = a_s[0] if n == 1 else jnp.concatenate(a_s, axis=1)
    if c % 16:
        t = _substitute(a, c)
        return [t[:, h * c:(h + 1) * c] for h in range(n)]
    half = c // 2
    lane = lax.broadcasted_iota(jnp.int32, (half, n * c), 1)
    left = lane % c < half                           # a matrix's first columns
    tp = _substitute(jnp.where(left, a[:half], a[half:]), half)   # T11 | T22
    zeros = jnp.zeros((half, n * c), F32)

    def rows_of(x, h, low):
        """x's lanes of matrix h's first columns, as rows h*c.. of a second
        operand: the upper half of the matrix's rows, or the lower."""
        mine = jnp.where(left & (lane // c == h), x, 0.0)
        return [zeros, mine] if low else [mine, zeros]

    def second(x, low):
        return jnp.concatenate(
            [part for h in range(n) for part in rows_of(x, h, low)], axis=0)

    x = _dot(jnp.where(left, a[half:], 0.0), second(tp, False), precision=HIGHEST)
    t21 = _dot(jnp.where(left, 0.0, tp), second(x, True), precision=HIGHEST)
    t = jnp.concatenate([jnp.where(left, tp, 0.0),
                         jnp.where(left, 0.0, tp) - t21], axis=0)
    return [t[:, h * c:(h + 1) * c] for h in range(n)]


def _heads(kk, rows, masks):
    """What both kernels build for the value heads of one chunk, from the
    (cum, beta) rows (1, C) of each: beta, exp(cum) and exp(cum_last - cum) as
    columns (C, 1), decay, T (float32)."""
    eye, lower, strict = masks
    cols, decays, a_s = [], [], []
    for cum_row, beta_row in rows:
        cum_col, beta_col = _to_col(cum_row, eye), _to_col(beta_row, eye)
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, cum_col - cum_row, 0.0)),
                          0.0)
        a_s.append((beta_col * kk) * jnp.where(strict, decay, 0.0))
        cols.append((beta_col, jnp.exp(cum_col), jnp.exp(cum_row[:, -1:] - cum_col)))
        decays.append(decay)
    return [col + (d, t) for col, d, t in zip(
        cols, decays, _unit_lower_inverses(a_s))]


def _over_chunks(chunk, chunks, together):
    """``chunk(i)`` for every chunk of the block, ``together`` of them a loop
    step: their chains of substitution steps are independent, and the
    scheduler fills one's waits with another's work."""
    if chunks % together:
        together = 1

    def several(j, _):
        for u in range(together):
            chunk(j * together + u)
        return 0

    lax.fori_loop(0, chunks // together, several, 0)


# A chunk's arithmetic as functions of arrays, under ``jax.jit``: a kernel's
# body is traced chunk by chunk and kernel by kernel (three forward calls and
# a backward one a layer), and every trace but the first of each function is
# then a look-up (on the chip's host the traces cost a step 23 s of set-up).

@jax.jit
def _fwd_chunk(q, k, vs, rows):
    """One chunk of one key head: q, k (C, Dk); a (C, Dv) and a
    ((1, C), (1, C)) pair of cum and beta rows a value head.  Returns
    ``w, u, qk, q_in, k_out`` a value head."""
    dtype = q.dtype
    masks = _masks(q.shape[0])
    qf, kf = q.astype(F32), k.astype(F32)
    kk, qk = _dot(k, k, NT), _dot(q, k, NT)
    out = []
    for v, (beta_col, e_col, out_col, decay, t) in zip(vs, _heads(kk, rows, masks)):
        t = t.astype(dtype)
        k_in = (kf * (beta_col * e_col)).astype(dtype)
        v_in = (v.astype(F32) * beta_col).astype(dtype)
        out.append((_dot(t, k_in).astype(dtype), _dot(t, v_in).astype(dtype),
                    (qk * decay).astype(dtype), (qf * e_col).astype(dtype),
                    (kf * out_col).astype(dtype)))
    return out


@jax.jit
def _bwd_chunk(q, k, vs, rows, cts):
    """The cotangents of :func:`_fwd_chunk`'s arguments from those of its
    results (``cts``: ``dw, du, dqk, dq_in, dk_out`` a value head): ``dq``,
    ``dk``, and ``dv``, ``dcum`` and ``dbeta`` a value head."""
    dtype, c = q.dtype, q.shape[0]
    masks = _masks(c)
    eye, _, strict = masks
    last_lane = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1

    def lanes(x):                       # (C, C) -> (C, 1)
        return jnp.sum(x, axis=1, keepdims=True)

    qf, kf = q.astype(F32), k.astype(F32)
    kk, qk = _dot(k, k, NT), _dot(q, k, NT)
    dq, dk = jnp.zeros_like(qf), jnp.zeros_like(kf)
    dkk, dqk = jnp.zeros_like(kk), jnp.zeros_like(qk)
    heads = []
    for v, (cum_row, beta_row), (dw, du, dqk_r, dq_in, dk_out), (
            beta_col, e_col, out_col, decay, t32) in zip(
                vs, rows, cts, _heads(kk, rows, masks)):
        e_row, out_row = jnp.exp(cum_row), jnp.exp(cum_row[:, -1:] - cum_row)
        t = t32.astype(dtype)
        k_in = (kf * (beta_col * e_col)).astype(dtype)
        v_in = (v.astype(F32) * beta_col).astype(dtype)

        # w = T k_in, u = T v_in
        dt = _dot(dw, k_in, NT) + _dot(du, v_in, NT)
        dk_in, dv_in = _dot(t, dw, TN), _dot(t, du, TN)
        # T = (I + A)^-1: dA = -T^T dT T^T, on the strict triangle
        da = jnp.where(
            strict, -_dot(_dot(t32, dt, TN, HIGHEST), t32, NT, HIGHEST), 0.0)
        # A = (beta kk) decay; qk = (q k^T) decay
        by_decay = da * decay
        dbeta_col = lanes(by_decay * kk)
        dkk += by_decay * beta_col
        dqk_r = dqk_r.astype(F32)
        dqk += dqk_r * decay
        ddiff = (da * (beta_col * kk) + dqk_r * qk) * decay
        dcum_col = lanes(ddiff)
        dcum_row = -jnp.sum(ddiff, axis=0, keepdims=True)
        # the rows' scalings (k_in, v_in, q_in, k_out): a row's sum over its
        # features is a diagonal entry of a product
        dk += dk_in * (beta_col * e_col) + dk_out.astype(F32) * out_col
        dq += dq_in.astype(F32) * e_col
        by_k_in = _diagonal(_dot(dk_in.astype(dtype), k, NT), eye)
        by_v_in = _diagonal(_dot(dv_in.astype(dtype), v, NT), eye)
        by_q_in = _diagonal(_dot(dq_in, q, NT), eye)
        by_out = _diagonal(_dot(dk_out, k, NT), eye) * out_row
        heads.append((
            (dv_in * beta_col).astype(dtype),
            dcum_row + _diagonal(dcum_col, eye)
            + (by_k_in * beta_row + by_q_in) * e_row - by_out
            + jnp.where(last_lane, jnp.sum(by_out), 0.0),
            _diagonal(dbeta_col, eye) + by_k_in * e_row + by_v_in))
    dqk, dkk = dqk.astype(dtype), dkk.astype(dtype)
    return ((dq + _dot(dqk, k)).astype(dtype),
            (dk + _dot(dqk, q, TN) + _dot(dkk, k) + _dot(dkk, k, TN)).astype(dtype),
            heads)


def _fwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, *out_refs):
    chunks, rep, c = out_refs[0].shape[0], out_refs[0].shape[3], out_refs[0].shape[4]
    dv = v_ref.shape[2] // rep

    def chunk(i):
        at = pl.ds(pl.multiple_of(i * c, c), c)
        heads = _fwd_chunk(
            q_ref[0, at, :], k_ref[0, at, :],
            [v_ref[0, at, r * dv:(r + 1) * dv] for r in range(rep)],
            [(cum_ref[0, 0, r, pl.ds(i, 1), :], beta_ref[0, 0, r, pl.ds(i, 1), :])
             for r in range(rep)])
        for r, results in enumerate(heads):          # w, u, qk, q_in, k_out
            for ref, x in zip(out_refs, results):
                ref[i, 0, 0, r] = x

    _over_chunks(chunk, chunks, 2)


def _bwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, *refs):
    ct_refs, (dcum_ref, dbeta_ref, dq_ref, dk_ref, dv_ref) = refs[:5], refs[5:]
    chunks, rep, c = ct_refs[0].shape[0], ct_refs[0].shape[3], ct_refs[0].shape[4]
    dv = v_ref.shape[2] // rep

    def chunk(i):
        at = pl.ds(pl.multiple_of(i * c, c), c)
        dq, dk, heads = _bwd_chunk(
            q_ref[0, at, :], k_ref[0, at, :],
            [v_ref[0, at, r * dv:(r + 1) * dv] for r in range(rep)],
            [(cum_ref[0, 0, r, pl.ds(i, 1), :], beta_ref[0, 0, r, pl.ds(i, 1), :])
             for r in range(rep)],
            [[ref[i, 0, 0, r] for ref in ct_refs] for r in range(rep)])
        dq_ref[0, at, :], dk_ref[0, at, :] = dq, dk
        for r, (dv_r, dcum, dbeta) in enumerate(heads):
            dv_ref[0, at, r * dv:(r + 1) * dv] = dv_r
            dcum_ref[0, 0, r, pl.ds(i, 1), :] = dcum
            dbeta_ref[0, 0, r, pl.ds(i, 1), :] = dbeta

    _over_chunks(chunk, chunks, 4)


def _specs(b, hk, rep, n, c, dk, dv):
    """The grid and the block specs by role: a key head's lanes of (B, S, ·),
    the lanes of the value heads it serves, a chunk-leading result or
    cotangent (N, B, Hk, R, C, width), a value head's row of C floats."""
    nb = CHUNKS if n % CHUNKS == 0 else n
    key = pl.BlockSpec((1, nb * c, dk), lambda bi, hi, ni: (bi, ni, hi))
    value = pl.BlockSpec((1, nb * c, rep * dv), lambda bi, hi, ni: (bi, ni, hi))

    def chunked(width):
        return pl.BlockSpec((nb, 1, 1, rep, c, width),
                            lambda bi, hi, ni: (ni, bi, hi, 0, 0, 0))

    row = pl.BlockSpec((1, 1, rep, nb, c), lambda bi, hi, ni: (bi, hi, 0, ni, 0))
    return (b, hk, n // nb), key, value, chunked, row


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _dims(q, v, cum):
    b, hk, rep, n, c = cum.shape
    return b, hk, rep, n, c, q.shape[2] // hk, v.shape[2] // (hk * rep)


def _prep_fwd(q, k, v, cum, beta, interpret):
    b, hk, rep, n, c, dk, dv = dims = _dims(q, v, cum)
    grid, key, value, chunked, row = _specs(*dims)
    return pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[key, key, value, row, row],
        out_specs=[chunked(dk), chunked(dv), chunked(c), chunked(dk), chunked(dk)],
        out_shape=[jax.ShapeDtypeStruct((n, b, hk, rep, c, d), v.dtype)
                   for d in (dk, dv, c, dk, dk)],
        compiler_params=_PARALLEL, interpret=interpret, name="gdn_prep_fwd",
    )(q, k, v, cum, beta)


def _prep_bwd(q, k, v, cum, beta, cts, interpret):
    b, hk, rep, n, c, dk, dv = dims = _dims(q, v, cum)
    grid, key, value, chunked, row = _specs(*dims)
    dcum, dbeta, dq, dk_, dv_ = pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[key, key, value, row, row,
                  chunked(dk), chunked(dv), chunked(c), chunked(dk), chunked(dk)],
        # the rows first: a chunk tensor at the head of the operation's text
        out_specs=[row, row, key, key, value],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (cum, beta, q, k, v)],
        compiler_params=_PARALLEL, interpret=interpret, name="gdn_prep_bwd",
    )(q, k, v, cum, beta, *cts)
    return dq, dk_, dv_, dcum, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn_prep(q, k, v, cum, beta, interpret):
    return tuple(_prep_fwd(q, k, v, cum, beta, interpret))


def _gdn_prep_fwd(q, k, v, cum, beta, interpret):
    return _gdn_prep(q, k, v, cum, beta, interpret), (q, k, v, cum, beta)


def _gdn_prep_bwd(interpret, inputs, cts):
    return _prep_bwd(*inputs, cts, interpret)


_gdn_prep.defvjp(_gdn_prep_fwd, _gdn_prep_bwd)


def gdn_prep(q, k, v, cum, beta, *, interpret: bool | None = None):
    """q, k: (B, S, Hk * Dk); v: (B, S, Hv * Dv), all of one compute dtype,
    S = N * C; cum (the log-decay summed inside each chunk) and beta:
    (B, Hk, R, N, C) float32.  Returns ``w, u, qk, q_in, k_out``, each
    (N, B, Hk, R, C, ·) in the compute dtype: what the scan over chunks is
    fed, less ``last``.  ``interpret`` None = off a TPU, as the flash kernels
    take it."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gdn_prep(q, k, v, cum, beta, interpret)
