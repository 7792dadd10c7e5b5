"""Pallas kernels of DeepSeek sparse attention's indexer: the selection of
each query's keys and the indexer's KL loss, never holding the (S, S)
scores in HBM.  ``ops/dsa.py`` states the arithmetic and holds the
``jnp`` forms these kernels are tested against.

Layouts (B batch, S positions, HI indexer heads of DI, H attention heads
of DA): indexer queries ``qi`` (B, HI, S, DI) and keys ``ki`` (B, S, DI)
in the compute dtype, weights ``w`` (B, S, HI) float32 with every constant
factor in them; attention queries and keys ``q``, ``k`` (B, H, S, DA) and
their rows' log-sum-exp ``lse`` (B, S, H) float32; the selection as
``flash_attention.pack``'s words (B, ceil(S / GROUP), S, LANES).

* ``dsa_select`` (grid (b, query block)): a block of rows' scores over
  every causal key in fast memory, the ``topk``-th largest by bisection
  over their bits (32 passes, then the positions of ties where a row has
  more than it takes), the chosen keys packed to words and the
  log-sum-exp of the chosen scores.
* ``dsa_kl_fwd`` / ``dsa_kl_dq`` (grid (b, query block, key block)) and
  ``dsa_kl_dk`` (grid (b, key block, query block)): each causal tile's
  scores and the attention's mean probability over the chosen keys,
  recomputed from their inputs; the forward sums each row's
  ``p log p - p I + p lse_I``, the backward passes give the gradient of
  ``sum(KL)`` with respect to ``qi`` and ``w`` (rows) and ``ki`` (keys).
  A tile above the diagonal is skipped and fetches nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpucfn.kernels.flash_attention import GROUP, LANES, WORD

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
INT_MIN, INT_MAX = -2 ** 31, 2 ** 31 - 1
VMEM_LIMIT = 100 * 2 ** 20


def _dot(a, b, dims=_NT):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def sortable(x):
    """float32 → int32 of the same order (-0.0 just below 0.0)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b >= 0, b, b ^ INT_MAX)


def unsortable(k):
    return lax.bitcast_convert_type(jnp.where(k >= 0, k, k ^ INT_MAX),
                                    jnp.float32)


def _column(x, j):
    """Column ``j`` (traced) of a (rows, n) tile as (rows, 1)."""
    pick = lax.broadcasted_iota(jnp.int32, x.shape, 1) == j
    return jnp.sum(jnp.where(pick, x, 0.0), axis=1, keepdims=True)


def _unpack(words, first: int | jax.Array, width: int):
    """(rows, LANES) words → (rows, width) bool of keys from bit ``first``."""
    return jnp.concatenate([jnp.right_shift(words, first + c) & 1
                            for c in range(width // LANES)], axis=1) != 0


# --------------------------------------------------------------------------
# selection
# --------------------------------------------------------------------------


def _select_kernel(q_ref, k_ref, w_ref, words_ref, lse_ref, key_ref, *,
                   topk: int, block_k: int):
    qi = pl.program_id(1)
    bq, width = key_ref.shape                        # width: whole word-rows
    hi, s = q_ref.shape[1], k_ref.shape[1]
    row = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    nkb = (qi * bq + bq - 1) // block_k + 1          # key blocks to the diagonal
    w = w_ref[0]                                     # (bq, HI) float32

    def fill(kb, _):
        start = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]

        def head(h, acc):
            a = _dot(q_ref[0, h], k)
            return acc + _column(w, h) * jnp.maximum(a, 0.0)

        acc = lax.fori_loop(0, hi, head, jnp.zeros((bq, block_k), jnp.float32))
        col = start + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        key_ref[:, pl.ds(start, block_k)] = sortable(
            jnp.where(col <= row, acc, -jnp.inf))
        return 0

    lax.fori_loop(0, nkb, fill, 0)

    # Past the diagonal the blocks filled hold the key of -inf, below every
    # score: no threshold the bisection settles on counts them, and while it
    # tries one below them every causal key counts as well.
    def count(pred):
        def body(kb, c):
            start = pl.multiple_of(kb * block_k, block_k)
            keys = key_ref[:, pl.ds(start, block_k)]
            col = start + lax.broadcasted_iota(jnp.int32, keys.shape, 1)
            return c + jnp.sum(pred(keys, col).astype(jnp.int32), axis=1,
                               keepdims=True)
        return lax.fori_loop(0, nkb, body, jnp.zeros((bq, 1), jnp.int32))

    take = jnp.minimum(topk, row + 1)

    def bisect(_, lohi):
        lo, hi_ = lohi
        mid = (lo >> 1) + (hi_ >> 1) + ((lo | hi_) & 1)     # ceil, no overflow
        ok = count(lambda k, c: k >= mid) >= take
        return jnp.where(ok, mid, lo), jnp.where(ok, hi_, mid - 1)

    thr, _ = lax.fori_loop(0, 32, bisect, (jnp.full((bq, 1), INT_MIN, jnp.int32),
                                           jnp.full((bq, 1), INT_MAX, jnp.int32)))
    need = take - count(lambda k, c: k > thr)
    tied = count(lambda k, c: k == thr)

    def cut_ties():
        # the ``need`` ties at the lowest positions: the least ``cut`` with
        # ``need`` ties before it
        def step(_, lohi):
            lo, hi_ = lohi
            mid = (lo + hi_) // 2
            ok = count(lambda k, c: (k == thr) & (c < mid)) >= need
            return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi_)
        _, cut = lax.fori_loop(0, s.bit_length(), step,
                               (jnp.zeros((bq, 1), jnp.int32),
                                jnp.full((bq, 1), s, jnp.int32)))
        return cut

    cut = lax.cond(jnp.any(tied > need), cut_ties,
                   lambda: jnp.full((bq, 1), s, jnp.int32))

    def top(kb, m):
        start = pl.multiple_of(kb * block_k, block_k)
        keys = key_ref[:, pl.ds(start, block_k)]
        return jnp.maximum(m, jnp.max(keys, axis=1, keepdims=True))

    m = unsortable(lax.fori_loop(0, nkb, top, jnp.full((bq, 1), INT_MIN,
                                                        jnp.int32)))

    def word_row(g, total):
        word = jnp.zeros((bq, LANES), jnp.int32)
        for j in range(WORD):
            start = g * GROUP + j * LANES
            keys = key_ref[:, pl.ds(pl.multiple_of(start, LANES), LANES)]
            col = start + lax.broadcasted_iota(jnp.int32, keys.shape, 1)
            chosen = (((keys > thr) | ((keys == thr) & (col < cut)))
                      & (col <= row))
            word = word | (chosen.astype(jnp.int32) << j)
            total = total + jnp.sum(
                jnp.where(chosen, jnp.exp(unsortable(keys) - m), 0.0),
                axis=1, keepdims=True)
        words_ref[0, g] = word
        return total

    total = lax.fori_loop(0, width // GROUP, word_row,
                          jnp.zeros((bq, 1), jnp.float32))
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(total), (bq, LANES))


def select(qi, ki, w, *, topk: int, block_q: int = 128, block_k: int = 512,
           interpret: bool = False):
    """(words (B, G, S, LANES) int32, lse (B, S) float32) of each row's
    ``min(topk, t + 1)`` largest causal scores, ties to the lower
    position.  S a multiple of both blocks."""
    b, hi, s, di = qi.shape
    if s % block_q or s % block_k:
        raise ValueError(f"S {s} must be a multiple of the blocks")
    g = -(-s // GROUP)
    words, lse = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k),
        grid=(b, s // block_q),
        in_specs=[pl.BlockSpec((1, hi, block_q, di), lambda bi, i: (bi, 0, i, 0)),
                  pl.BlockSpec((1, s, di), lambda bi, i: (bi, 0, 0)),
                  pl.BlockSpec((1, block_q, hi), lambda bi, i: (bi, i, 0))],
        out_specs=[pl.BlockSpec((1, g, block_q, LANES),
                                lambda bi, i: (bi, 0, i, 0)),
                   pl.BlockSpec((1, block_q, LANES), lambda bi, i: (bi, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, g, s, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((b, s, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, g * GROUP), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_select",
    )(qi, ki, w)
    return words, lse[..., 0]


# --------------------------------------------------------------------------
# the indexer's KL loss
# --------------------------------------------------------------------------


def _scores(lhs, rhs, w_of, hi: int, rows: int, cols: int):
    """Σ_h w_h ReLU(qi_h · k) over a tile made as ``lhs(h) · rhs(h)ᵀ``:
    ``w_of(h)`` broadcasts head h's weights along the tile's query axis."""
    def head(h, acc):
        return acc + w_of(h) * jnp.maximum(_dot(lhs(h), rhs(h)), 0.0)
    return lax.fori_loop(0, hi, head, jnp.zeros((rows, cols), jnp.float32))


def _mean_probs(qa_ref, ka_ref, lse_of, heads: int, rows: int, cols: int,
                scale: float):
    def head(a, acc):
        return acc + jnp.exp(_dot(qa_ref(a), ka_ref(a)) * scale - lse_of(a))
    return lax.fori_loop(0, heads, head,
                         jnp.zeros((rows, cols), jnp.float32)) / heads


def _kl_rows_kernel(qi_ref, ki_ref, w_ref, qa_ref, ka_ref, lse_ref, lsei_ref,
                    words_ref, *rest, scale: float, grads: bool):
    """Grid (b, qi, ki): the forward's row sums, or (``grads``) the rows'
    gradients of ``sum(KL)`` with respect to ``qi`` and ``w``."""
    qb, kb = pl.program_id(1), pl.program_id(2)
    hi, bq, _ = qi_ref.shape[1:]
    h, bk = qa_ref.shape[1], ka_ref.shape[2]
    if grads:
        dq_ref, dw_ref, dq_acc, dw_acc = rest
    else:
        kl_ref, acc = rest

    @pl.when(kb == 0)
    def _init():
        if grads:
            dq_acc[...] = jnp.zeros_like(dq_acc)
            dw_acc[...] = jnp.zeros_like(dw_acc)
        else:
            acc[...] = jnp.zeros_like(acc)

    @pl.when(kb <= qb)
    def _tile():
        w, k_idx = w_ref[0], ki_ref[0]
        chosen = _unpack(words_ref[0, 0], kb * bk % GROUP // LANES, bk)
        scores = _scores(lambda j: qi_ref[0, j], lambda j: k_idx,
                         lambda j: _column(w, j), hi, bq, bk)
        lse = lse_ref[0]
        p = _mean_probs(lambda a: qa_ref[0, a], lambda a: ka_ref[0, a],
                        lambda a: _column(lse, a), h, bq, bk, scale)
        p = jnp.where(chosen, p, 0.0)
        lse_i = lsei_ref[0][:, :1]
        if not grads:
            plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
            term = plogp - p * (jnp.where(chosen, scores, 0.0) - lse_i)
            acc[...] += jnp.broadcast_to(jnp.sum(term, axis=1, keepdims=True),
                                         acc.shape)
            return
        d = jnp.where(chosen, jnp.exp(scores - lse_i) - p, 0.0)
        lane = lax.broadcasted_iota(jnp.int32, dw_acc.shape, 1)

        def head(j, _):
            a = _dot(qi_ref[0, j], k_idx)
            dw_acc[...] += jnp.where(lane == j, jnp.sum(
                jnp.maximum(a, 0.0) * d, axis=1, keepdims=True), 0.0)
            ds = jnp.where(a > 0, d, 0.0) * _column(w, j)
            dq_acc[j] += _dot(ds.astype(k_idx.dtype), k_idx, _NN)
            return 0

        lax.fori_loop(0, hi, head, 0)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        if grads:
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
            dw_ref[0] = dw_acc[...]
        else:
            kl_ref[0] = acc[...]


def _kl_keys_kernel(ki_ref, ka_ref, qi_ref, w_ref, qa_ref, lse_ref, lsei_ref,
                    words_ref, dk_ref, dk_acc, *, scale: float):
    """Grid (b, ki, qi): the keys' gradient of ``sum(KL)`` with respect to
    ``ki``, every tile formed transposed (keys as rows)."""
    kb, qb = pl.program_id(1), pl.program_id(2)
    hi, bq = qi_ref.shape[1], qi_ref.shape[2]
    h, bk = ka_ref.shape[1], ka_ref.shape[2]

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)

    @pl.when(qb >= kb)
    def _tile():
        k_idx = ki_ref[0]
        chosen = _unpack(words_ref[0, 0], kb * bk % GROUP // LANES,
                         bk).astype(jnp.int32).T != 0            # (BK, BQ)
        scores = _scores(lambda j: k_idx, lambda j: qi_ref[0, j],
                         lambda j: w_ref[0, j], hi, bk, bq)
        p = _mean_probs(lambda a: ka_ref[0, a], lambda a: qa_ref[0, a],
                        lambda a: lse_ref[0, a], h, bk, bq, scale)
        d = jnp.where(chosen, jnp.exp(scores - lsei_ref[0]) - p, 0.0)

        def head(j, _):
            q_idx = qi_ref[0, j]
            a = _dot(k_idx, q_idx)
            ds = jnp.where(a > 0, d, 0.0) * w_ref[0, j]
            dk_acc[...] += _dot(ds.astype(q_idx.dtype), q_idx, _NN)
            return 0

        lax.fori_loop(0, hi, head, 0)

    @pl.when(qb == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)


def _params(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=VMEM_LIMIT)


def kl_rows(qi, ki, w, q, k, lse, lse_i, words, *, scale: float, block: int,
            grads: bool, interpret: bool = False):
    """Grid (b, qi, ki).  Without ``grads``: each row's KL (B, S).  With:
    the gradient of ``sum(KL)`` with respect to ``qi`` (B, HI, S, DI) and
    ``w`` (B, S, HI)."""
    b, hi, s, di = qi.shape
    h, da = q.shape[1], q.shape[-1]
    n = s // block
    held = lambda i, j: jnp.minimum(i, j)  # noqa: E731 — above the diagonal
    in_specs = [
        pl.BlockSpec((1, hi, block, di), lambda bi, i, j: (bi, 0, i, 0)),
        pl.BlockSpec((1, block, di), lambda bi, i, j: (bi, held(i, j), 0)),
        pl.BlockSpec((1, block, hi), lambda bi, i, j: (bi, i, 0)),
        pl.BlockSpec((1, h, block, da), lambda bi, i, j: (bi, 0, i, 0)),
        pl.BlockSpec((1, h, block, da), lambda bi, i, j: (bi, 0, held(i, j), 0)),
        pl.BlockSpec((1, block, h), lambda bi, i, j: (bi, i, 0)),
        pl.BlockSpec((1, block, LANES), lambda bi, i, j: (bi, i, 0)),
        pl.BlockSpec((1, 1, block, LANES), lambda bi, i, j: (
            bi, held(i, j) * block // GROUP, i, 0)),
    ]
    args = (qi, ki, w, q, k, lse, jnp.broadcast_to(lse_i[..., None],
                                                    (b, s, LANES)), words)
    row = pl.BlockSpec((1, block, LANES), lambda bi, i, j: (bi, i, 0))
    if grads:
        out_specs = [pl.BlockSpec((1, hi, block, di), lambda bi, i, j: (bi, 0, i, 0)),
                     row]
        out_shape = [jax.ShapeDtypeStruct(qi.shape, jnp.float32),
                     jax.ShapeDtypeStruct((b, s, LANES), jnp.float32)]
        scratch = [pltpu.VMEM((hi, block, di), jnp.float32),
                   pltpu.VMEM((block, LANES), jnp.float32)]
    else:
        out_specs = [row]
        out_shape = [jax.ShapeDtypeStruct((b, s, LANES), jnp.float32)]
        scratch = [pltpu.VMEM((block, LANES), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kl_rows_kernel, scale=scale, grads=grads),
        grid=(b, n, n), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="dsa_kl_dq" if grads else "dsa_kl_fwd",
    )(*args)
    if grads:
        return out[0], out[1][..., :hi]
    return out[0][..., 0]


def kl_keys(qi, ki, w, q, k, lse, lse_i, words, *, scale: float, block: int,
            interpret: bool = False):
    """Grid (b, ki, qi): the gradient of ``sum(KL)`` with respect to ``ki``
    (B, S, DI)."""
    b, hi, s, di = qi.shape
    h, da = q.shape[1], q.shape[-1]
    n = s // block
    held = lambda i, j: jnp.maximum(i, j)  # noqa: E731 — above the diagonal
    in_specs = [
        pl.BlockSpec((1, block, di), lambda bi, i, j: (bi, i, 0)),
        pl.BlockSpec((1, h, block, da), lambda bi, i, j: (bi, 0, i, 0)),
        pl.BlockSpec((1, hi, block, di), lambda bi, i, j: (bi, 0, held(i, j), 0)),
        pl.BlockSpec((1, hi, 1, block), lambda bi, i, j: (bi, 0, 0, held(i, j))),
        pl.BlockSpec((1, h, block, da), lambda bi, i, j: (bi, 0, held(i, j), 0)),
        pl.BlockSpec((1, h, 1, block), lambda bi, i, j: (bi, 0, 0, held(i, j))),
        pl.BlockSpec((1, 1, block), lambda bi, i, j: (bi, 0, held(i, j))),
        pl.BlockSpec((1, 1, block, LANES), lambda bi, i, j: (
            bi, i * block // GROUP, held(i, j), 0)),
    ]
    args = (ki, k, qi, jnp.swapaxes(w, 1, 2)[:, :, None, :], q,
            jnp.swapaxes(lse, 1, 2)[:, :, None, :], lse_i[:, None, :], words)
    return pl.pallas_call(
        functools.partial(_kl_keys_kernel, scale=scale),
        grid=(b, n, n), in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block, di), lambda bi, i, j: (bi, i, 0))],
        out_shape=[jax.ShapeDtypeStruct(ki.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, di), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="dsa_kl_dk",
    )(*args)[0]
