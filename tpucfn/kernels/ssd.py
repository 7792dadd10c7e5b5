"""The state-space recurrence's chunk-local parts as two Pallas kernel pairs.

What :func:`tpucfn.ops.ssd.ssd` computes with ``jnp`` on either side of its
scan over chunks, computed with a chunk's working set in VMEM:

* :func:`ssd_own` (``ssd_own_fwd``, ``ssd_own_bwd``), before the scan: what each
  chunk adds to its heads' states, ``(x * exp(l_L - l_j) dt_j)^T B`` (in
  ``jnp`` a float32 copy of x, its scaled copy in the compute dtype and their
  re-tiling, through HBM);
* :func:`ssd_chunk` (``ssd_chunk_fwd``, ``ssd_chunk_bwd``), after it: every
  chunk's outputs from the state it starts from: a group's ``C_i . B_j``, each
  head's decays ``exp(l_i - l_j)``, their product with ``dt_j`` rounded to the
  compute dtype, ``m @ x``, the state's part ``exp(l_i) C_i before^T`` and ``D
  x`` (in ``jnp`` for every head of every chunk one float32 ``(L, L)`` tensor
  after another through HBM).

One grid step serves one chunk of one group and ``heads`` of the heads it
serves (the last grid axis walks the group's heads: ``C B^T`` is made at its
first step, into scratch, once a group and chunk).  ``x``, ``B`` and ``C`` are
read where the layer left them, ``(B, S, H * P)`` and ``(B, S, G * N)``, and
``y`` is written so: a block's index picks the chunk's rows and the heads'
lanes, and no transpose stands between the layer and the kernels.  Heads
narrower than the 128 lanes are worked off a lane tile at a time (two heads of
64): each head's ``m`` is its own, so the tile's product is ``[m_0 | m_1] @
[x_0; x_1]`` with the other head's lanes of ``x`` zeroed, one product whose
contraction sums the heads' parts where their lanes meet.

The arithmetic is the ``jnp`` forms', rounded where they round: log-decays,
decays and ``C B^T`` float32; every decay factor ``exp`` of a number that is
never positive (``l_i - l_j`` on and below the diagonal, nothing factored); a
position's factor on itself the constant 1, which carries no gradient; ``m``,
the scaled x and the state in the compute dtype for the products, which
accumulate in float32; ``y`` summed in float32 and rounded once.  The
cumulative sums, ``exp`` of the per-position scalars and the scan over chunks
stay outside.

Both carry a ``jax.custom_vjp`` whose residuals are the inputs: the backward
kernels rebuild ``C B^T``, the decays, ``m`` and the scaled x in VMEM and
return every cotangent (``dM = dy x^T``, ``dx = m^T dy``; ``d(C B^T)`` and
``dB`` summed over the group's heads in float32 scratch until the group's last
step; the decays' cotangent ``dM * m`` below the diagonal as row sums onto
``l_i`` and column sums off ``l_j``).  A head's per-position scalars come in as
rows ``(1, L)`` and as columns ``(L, 1)``, and their cotangents go out so: XLA
transposes 4 MB, the kernels nothing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128

NN = (((1,), (0,)), ((), ()))   # x @ y
NT = (((1,), (1,)), ((), ()))   # x @ y^T
TN = (((0,), (0,)), ((), ()))   # x^T @ y


def _dot(x, y, dims=NN):
    return lax.dot_general(x, y, dims, preferred_element_type=F32)


def heads_a_tile(p: int) -> int:
    """How many heads of ``p`` channels one 128-lane tile of x holds."""
    return max(1, LANES // p)


def heads_a_step(r: int, p: int) -> int:
    """How many of a group's ``r`` heads one grid step serves: the fewest that
    are whole sublane tiles of the heads' rows (8) and whole lane tiles of x,
    or all of them."""
    return next((h for h in range(8, r, 8)
                 if r % h == 0 and (h * p) % LANES == 0), r)


def _lower(n):
    """Where (n, n) lies on or below the diagonal, and strictly below it."""
    row = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return row >= col, row > col


def _masked_cb(c, b):
    """``C B^T`` of a chunk (L, L) float32, zero above the diagonal: the mask
    of every head's decays, laid on once a group and chunk."""
    cb = _dot(c, b, NT)
    return jnp.where(_lower(cb.shape[0])[0], cb, 0.0)


def _decay(cum_row, cum_col):
    """exp(l_i - l_j) on and below the diagonal (so the constant 1 on it: l_i
    - l_i is 0, and the backward kernel sends nothing back through it), and
    exp(0) above, where ``C B^T`` is zero: (L, L) float32, no entry over 1."""
    return jnp.exp(jnp.minimum(cum_col - cum_row, 0.0))


def _mine(k, w):
    """For each of a tile's ``k`` heads, which of its ``w`` lanes are the
    head's: (1, w) masks, or None where the tile is one head's."""
    if k == 1:
        return [None]
    head = lax.broadcasted_iota(jnp.int32, (1, w), 1) // (w // k)
    return [head == q for q in range(k)]


def _only(mask, t):
    return t if mask is None else jnp.where(mask, t, jnp.zeros_like(t))


def _by_head(masks, cols):
    """(L, 1) columns, one a head, as (L, w): each over its head's lanes."""
    out = cols[0]
    for mask, col in zip(masks[1:], cols[1:]):
        out = jnp.where(mask, col, out)
    return out


# A lane tile's arithmetic as functions of arrays, under ``jax.jit``: a kernel's
# body is traced once a ``pallas_call`` (two forward calls and a backward one a
# layer) and a step's tiles one by one, and every trace of a function but its
# first is then a look-up (kernels/gated_delta.py; PERF.md, PR 28).

@jax.jit
def _fwd_tile(cb, c, x, before, d, rows):
    """cb (L, L) float32, masked; c (L, N); x (L, w) and before (w, N), the tile's
    heads side by side; d (1, w) float32; ``rows`` a (dt (1, L), l (1, L),
    l (L, 1)) triple a head.  Returns y (L, w) in ``x.dtype``."""
    dtype = x.dtype
    masks = _mine(len(rows), x.shape[1])
    ms, xs = [], []
    for mask, (dt_row, cum_row, cum_col) in zip(masks, rows):
        ms.append((cb * _decay(cum_row, cum_col) * dt_row).astype(dtype))
        xs.append(_only(mask, x))
    y = _dot(jnp.concatenate(ms, axis=1), jnp.concatenate(xs, axis=0))
    y = y + _dot(c, before, NT) * _by_head(
        masks, [jnp.exp(cum_col) for _, _, cum_col in rows])
    return (y + x.astype(F32) * d).astype(dtype)


@jax.jit
def _bwd_tile(cb, c, x, before, d, dy, rows):
    """The cotangents of :func:`_fwd_tile`'s arguments from y's: ``dx`` (L, w)
    and ``dbefore`` (w, N) in the compute dtype, ``dcb`` (L, L; its entries
    above the diagonal are no one's: the caller masks the sum) and the state's
    part of ``dc`` (L, N) float32, both summed over the tile's heads, ``dd``
    (1, w) float32, and a (ddt (1, L), dl (1, L), dl (L, 1)) triple a head."""
    dtype = x.dtype
    masks = _mine(len(rows), x.shape[1])
    dyf, xf = dy.astype(F32), x.astype(F32)
    dd = jnp.sum(dyf * xf, axis=0, keepdims=True)

    # the state's part: y += exp(l_i) (c @ before^T)
    e_cols = [jnp.exp(cum_col) for _, _, cum_col in rows]
    e = _by_head(masks, e_cols)
    by_e = dyf * _dot(c, before, NT)
    dy_e = (dyf * e).astype(dtype)
    dc = _dot(dy_e, before)
    dbefore = _dot(dy_e, c, TN).astype(dtype)

    dcb = jnp.zeros_like(cb)
    strict = _lower(cb.shape[0])[1]
    ms, dys, heads = [], [], []
    for mask, e_col, (dt_row, cum_row, cum_col) in zip(masks, e_cols, rows):
        decay = _decay(cum_row, cum_col)
        ms.append((cb * decay * dt_row).astype(dtype))
        dys.append(_only(mask, dy))
        # m = (cb decay) dt, by its three factors (dcb: masked by the caller)
        by_decay = _dot(dy, _only(mask, x), NT) * decay
        by_dt = by_decay * cb
        dcb = dcb + by_decay * dt_row
        # exp(l_i - l_j) below the diagonal: onto l_i by rows, off l_j by columns
        ddiff = jnp.where(strict, by_dt * dt_row, 0.0)
        heads.append((
            jnp.sum(by_dt, axis=0, keepdims=True),
            -jnp.sum(ddiff, axis=0, keepdims=True),
            jnp.sum(ddiff, axis=1, keepdims=True)
            + jnp.sum(_only(mask, by_e), axis=1, keepdims=True) * e_col))
    dx = _dot(jnp.concatenate(ms, axis=0), jnp.concatenate(dys, axis=0), TN)
    return (dx + dyf * d).astype(dtype), dbefore, dcb, dc, dd, heads


def _tiles(x_ref, col_ref):
    """A grid step's lane tiles: for each, its lanes of x, which of the step's
    heads it holds and their (L, 1) columns."""
    heads = col_ref.shape[5]
    p = x_ref.shape[2] // heads
    k = math.gcd(heads, heads_a_tile(p))
    cols = col_ref[0, 0, 0, 0]                                    # (L, heads)
    for u in range(heads // k):
        hs = range(u * k, (u + 1) * k)
        yield slice(u * k * p, (u + 1) * k * p), hs, [cols[:, h:h + 1] for h in hs]


def _rows(hs, cols, dt_ref, cum_ref):
    """The (dt row, l row, l column) triples of a tile's heads."""
    return [(dt_ref[0, 0, 0, h:h + 1, :], cum_ref[0, 0, 0, h:h + 1, :], col)
            for h, col in zip(hs, cols)]


def _columns_out(ref, columns):
    """An (L, 1) column a head of the step, written as the block (L, heads)."""
    lane = lax.broadcasted_iota(jnp.int32, ref.shape[4:], 1)
    out = jnp.zeros(ref.shape[4:], F32)
    for h, col in enumerate(columns):
        out = jnp.where(lane == h, col, out)
    ref[0, 0, 0, 0] = out


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, col_ref, before_ref,
                d_ref, y_ref, cb_ref):
    @pl.when(pl.program_id(3) == 0)
    def _():
        cb_ref[...] = _masked_cb(c_ref[0], b_ref[0])

    for at, hs, cols in _tiles(x_ref, col_ref):
        y_ref[0, :, at] = _fwd_tile(
            cb_ref[...], c_ref[0], x_ref[0, :, at], before_ref[0, 0, 0, at, :],
            d_ref[:, at], _rows(hs, cols, dt_ref, cum_ref))


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, col_ref, before_ref,
                d_ref, dy_ref, ddt_ref, dcum_ref, dcol_ref, dd_ref, dx_ref,
                db_ref, dc_ref, dbefore_ref, cb_ref, dcb_ref, dc_sum_ref):
    hi = pl.program_id(3)

    @pl.when(hi == 0)
    def _():
        cb_ref[...] = _masked_cb(c_ref[0], b_ref[0])
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        dc_sum_ref[...] = jnp.zeros_like(dc_sum_ref)

    dcols = []
    for at, hs, cols in _tiles(x_ref, col_ref):
        dx, dbefore, dcb, dc, dd, per_head = _bwd_tile(
            cb_ref[...], c_ref[0], x_ref[0, :, at], before_ref[0, 0, 0, at, :],
            d_ref[:, at], dy_ref[0, :, at], _rows(hs, cols, dt_ref, cum_ref))
        dx_ref[0, :, at] = dx
        dbefore_ref[0, 0, 0, at, :] = dbefore
        dd_ref[0, 0, :, at] = dd
        dcb_ref[...] += dcb
        dc_sum_ref[...] += dc
        for h, (ddt, dcum, dcol) in zip(hs, per_head):
            ddt_ref[0, 0, 0, h:h + 1, :] = ddt
            dcum_ref[0, 0, 0, h:h + 1, :] = dcum
            dcols.append(dcol)
    _columns_out(dcol_ref, dcols)

    @pl.when(hi == pl.num_programs(3) - 1)
    def _():
        dcb = jnp.where(_lower(dcb_ref.shape[0])[0], dcb_ref[...], 0.0).astype(
            b_ref.dtype)
        dc_ref[0] = (dc_sum_ref[...] + _dot(dcb, b_ref[0])).astype(dc_ref.dtype)
        db_ref[0] = _dot(dcb, c_ref[0], TN).astype(db_ref.dtype)


@jax.jit
def _own_tile(b, x, cols):
    """What a chunk adds to its heads' states: b (L, N); x (L, w), the tile's
    heads side by side; ``cols`` an (L, 1) column a head, ``exp(l_L - l_j)
    dt_j``.  Returns (w, N) float32."""
    scaled = x.astype(F32) * _by_head(_mine(len(cols), x.shape[1]), cols)
    return _dot(scaled.astype(x.dtype), b, TN)


@jax.jit
def _own_bwd_tile(b, x, cols, down):
    """The cotangents of :func:`_own_tile`'s arguments from its result's (w,
    N): ``dx`` (L, w) in the compute dtype, ``db`` (L, N) float32, summed over
    the tile's heads, and an (L, 1) column a head."""
    dtype = x.dtype
    masks = _mine(len(cols), x.shape[1])
    to_end, xf, down = _by_head(masks, cols), x.astype(F32), down.astype(dtype)
    by_scaled = _dot(b, down, NT)                                  # (L, w)
    by_to_end = xf * by_scaled
    return ((by_scaled * to_end).astype(dtype),
            _dot((xf * to_end).astype(dtype), down),
            [jnp.sum(_only(mask, by_to_end), axis=1, keepdims=True)
             for mask in masks])


def _own_kernel(x_ref, b_ref, col_ref, own_ref):
    for at, _, cols in _tiles(x_ref, col_ref):
        own_ref[0, 0, 0, at, :] = _own_tile(b_ref[0], x_ref[0, :, at], cols)


def _own_bwd_kernel(x_ref, b_ref, col_ref, down_ref, dcol_ref, dx_ref, db_ref,
                    db_sum_ref):
    hi = pl.program_id(3)

    @pl.when(hi == 0)
    def _():
        db_sum_ref[...] = jnp.zeros_like(db_sum_ref)

    dcols = []
    for at, _, cols in _tiles(x_ref, col_ref):
        dx, db, per_head = _own_bwd_tile(
            b_ref[0], x_ref[0, :, at], cols, down_ref[0, 0, 0, at, :])
        dx_ref[0, :, at] = dx
        db_sum_ref[...] += db
        dcols += per_head
    _columns_out(dcol_ref, dcols)

    @pl.when(hi == pl.num_programs(3) - 1)
    def _():
        db_ref[0] = db_sum_ref[...].astype(db_ref.dtype)


# the group's heads last and in order: C B^T and the sums over a group's heads
# live in scratch from its first step to its last
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _specs(x, b, cols):
    """The grid and the block specs by role: the heads' lanes of x's chunk
    (B, S, H * P); a group's lanes of B's and C's (B, S, G * N); the heads'
    rows (B, Nc, G, R, L) and columns (B, Nc, G, R / heads, L, heads); their
    incoming states, or own sums, (Nc, B, G, R * P, N); their lanes of D (1, H *
    P) and of its cotangent a chunk (B, Nc, 1, H * P)."""
    bsz, nc, g, steps, L, heads = cols.shape
    wide, n = x.shape[2] // (g * steps), b.shape[2] // g
    grid = (bsz, g, nc, steps)
    return grid, dict(
        x=pl.BlockSpec((1, L, wide), lambda bi, gi, ni, hi: (bi, ni, gi * steps + hi)),
        group=pl.BlockSpec((1, L, n), lambda bi, gi, ni, hi: (bi, ni, gi)),
        rows=pl.BlockSpec((1, 1, 1, heads, L),
                          lambda bi, gi, ni, hi: (bi, ni, gi, hi, 0)),
        cols=pl.BlockSpec((1, 1, 1, 1, L, heads),
                          lambda bi, gi, ni, hi: (bi, ni, gi, hi, 0, 0)),
        before=pl.BlockSpec((1, 1, 1, wide, n),
                            lambda bi, gi, ni, hi: (ni, bi, gi, hi, 0)),
        d=pl.BlockSpec((1, wide), lambda bi, gi, ni, hi: (0, gi * steps + hi)),
        dd=pl.BlockSpec((1, 1, 1, wide),
                        lambda bi, gi, ni, hi: (bi, ni, 0, gi * steps + hi)))


def _fwd(x, b, c, dt, cum, cols, before, d, interpret):
    grid, s = _specs(x, b, cols)
    L = cum.shape[4]
    return pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[s["x"], s["group"], s["group"], s["rows"], s["rows"],
                  s["cols"], s["before"], s["d"]],
        out_specs=s["x"], out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((L, L), F32)],
        compiler_params=_SEMANTICS, interpret=interpret, name="ssd_chunk_fwd",
    )(x, b, c, dt, cum, cols, before, d)


def _bwd(x, b, c, dt, cum, cols, before, d, dy, interpret):
    grid, s = _specs(x, b, cols)
    (bsz, nc), L, n = cum.shape[:2], cum.shape[4], before.shape[4]
    ddt, dcum, dcols, dd, dx, db, dc, dbefore = pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[s["x"], s["group"], s["group"], s["rows"], s["rows"],
                  s["cols"], s["before"], s["d"], s["x"]],
        out_specs=[s["rows"], s["rows"], s["cols"], s["dd"], s["x"],
                   s["group"], s["group"], s["before"]],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (dt, cum, cols)]
        + [jax.ShapeDtypeStruct((bsz, nc, 1, x.shape[2]), F32)]
        + [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (x, b, c, before)],
        scratch_shapes=[pltpu.VMEM((L, L), F32), pltpu.VMEM((L, L), F32),
                        pltpu.VMEM((L, n), F32)],
        compiler_params=_SEMANTICS, interpret=interpret, name="ssd_chunk_bwd",
    )(x, b, c, dt, cum, cols, before, d, dy)
    return dx, db, dc, ddt, dcum, dcols, dbefore, jnp.sum(dd, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _ssd_chunk(x, b, c, dt, cum, cols, before, d, interpret):
    return _fwd(x, b, c, dt, cum, cols, before, d, interpret)


def _ssd_chunk_fwd(x, b, c, dt, cum, cols, before, d, interpret):
    inputs = (x, b, c, dt, cum, cols, before, d)
    return _fwd(*inputs, interpret), inputs


def _ssd_chunk_bwd(interpret, inputs, dy):
    return _bwd(*inputs, dy, interpret)


_ssd_chunk.defvjp(_ssd_chunk_fwd, _ssd_chunk_bwd)


def _own_fwd_call(x, b, cols, interpret):
    grid, s = _specs(x, b, cols)
    bsz, nc, g = cols.shape[:3]
    return pl.pallas_call(
        _own_kernel, grid=grid, in_specs=[s["x"], s["group"], s["cols"]],
        out_specs=s["before"], out_shape=jax.ShapeDtypeStruct(
            (nc, bsz, g, x.shape[2] // g, b.shape[2] // g), F32),
        compiler_params=_SEMANTICS, interpret=interpret, name="ssd_own_fwd",
    )(x, b, cols)


def _own_bwd_call(x, b, cols, down, interpret):
    grid, s = _specs(x, b, cols)
    dcols, dx, db = pl.pallas_call(
        _own_bwd_kernel, grid=grid,
        in_specs=[s["x"], s["group"], s["cols"], s["before"]],
        out_specs=[s["cols"], s["x"], s["group"]],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (cols, x, b)],
        scratch_shapes=[pltpu.VMEM((cols.shape[4], b.shape[2] // cols.shape[2]), F32)],
        compiler_params=_SEMANTICS, interpret=interpret, name="ssd_own_bwd",
    )(x, b, cols, down)
    return dx, db, dcols


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ssd_own(x, b, cols, interpret):
    return _own_fwd_call(x, b, cols, interpret)


def _ssd_own_fwd(x, b, cols, interpret):
    return _own_fwd_call(x, b, cols, interpret), (x, b, cols)


def _ssd_own_bwd(interpret, inputs, down):
    return _own_bwd_call(*inputs, down, interpret)


_ssd_own.defvjp(_ssd_own_fwd, _ssd_own_bwd)


def _interpret(interpret):
    """None = off a TPU, as the flash kernels take it."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _columns(t, heads):
    """(B, Nc, L, G, R) as the columns a grid step reads: (B, Nc, G, R /
    heads, L, heads)."""
    bsz, nc, L, g, r = t.shape
    return t.reshape(bsz, nc, L, g, r // heads, heads).transpose(0, 1, 3, 4, 2, 5)


def ssd_own(x, b, to_end, p: int, *, interpret: bool | None = None):
    """What each chunk adds to its heads' states, ``sum_j to_end_j x_j B_j^T``:
    x: (B, S, H * P), heads of ``p`` channels; b: (B, S, G * N), both of one
    compute dtype, S = Nc * L; to_end: (B, Nc, L, G, R) float32, ``exp(l_L -
    l_j) dt_j``.  Returns (Nc, B, G, R, P, N) float32: what the scan over
    chunks is fed.  Its backward kernel returns the cotangents of all
    three."""
    bsz, nc, L, g, r = to_end.shape
    own = _ssd_own(x, b, _columns(to_end, heads_a_step(r, p)),
                   _interpret(interpret))
    return own.reshape(nc, bsz, g, r, p, own.shape[4])


def ssd_chunk(x, b, c, dt, cum, before, d, *, interpret: bool | None = None):
    """x: (B, S, H * P); b, c: (B, S, G * N); before: (Nc, B, G, R, P, N), the
    state each chunk starts from; all of one compute dtype, S = Nc * L.  dt
    and cum (the log-decay summed inside each chunk): (B, Nc, L, G, R)
    float32, as ``ssd`` holds them.  d: (H,) float32.  Returns the chunks'
    outputs, chunk-local part, state's part and ``d x``: (B, S, H * P) in the
    compute dtype."""
    bsz, nc, L, g, r = cum.shape
    p, n = before.shape[4], before.shape[5]
    rows = lambda t: jnp.moveaxis(t, 2, -1)  # noqa: E731  (B,Nc,G,R,L)
    return _ssd_chunk(x, b, c, rows(dt), rows(cum),
                      _columns(cum, heads_a_step(r, p)),
                      before.reshape(nc, bsz, g, r * p, n),
                      jnp.repeat(d.astype(F32), p)[None], _interpret(interpret))
