"""FlashAttention-2 for TPU in Pallas: fused blockwise attention.

The memory-bound op the reference delegated to cuDNN gets a TPU-native
kernel: O(S·D) memory instead of O(S²) — logits never leave VMEM, online
softmax streams KV blocks through the MXU (pallas_guide.md blockwise
pattern). Forward emits (O, LSE); backward is two more Pallas kernels
(dQ; dK/dV) in the FlashAttention-2 formulation wired through
``jax.custom_vjp``.

**A block step does only what its place on the grid needs.** Everything
is decided from facts known at trace time (``causal``, the static
offsets, whether segment ids came, the true key count against the padded
one, the block sizes, the arrays' dtype) or from ``program_id``
(:class:`_Grid`). A block of the (query block, key block) grid is

* *skipped*: causal, and its first key is later than its last query. No
  MXU work, and no fetch either: the index maps of the streamed side
  clamp to the nearest block that is needed, which is already resident
  (key blocks in the forward and the query backward, query-side blocks
  in the key/value backward).
* *interior*: no pair of it is masked (its last key is not later than its
  first query, it holds no padded key, no segment ids). No mask is
  built, and the probabilities are a plain ``exp(s - m)``.
* *edge*: everything else (the diagonal, the padded last key block,
  every block under segment ids). Only the parts of the mask that can be
  live are built, and the guard that zeroes a fully masked row's junk
  probabilities stays only where such a row can exist (segment ids, or a
  diagonal shifted so that a query precedes every key it was given).

The two bodies are one function under two ``pl.when``, each traced once.
At S 8,192 with the table's 1,024 × 1,024 blocks a causal head makes 64
grid steps: 28 interior, 8 edge, 28 skipped (``_Grid.count``). A grid
step costs 0.3–0.5 µs on a v5e whatever it computes, so blocks are as
large as fast memory allows once the per-element work is small.

**The dtype rule.** Products run in the arrays' own dtype with float32
accumulation (``preferred_element_type``): q, k, v and ``do`` go to the
MXU as they arrive, the probabilities and ``ds`` are cast to that dtype
for their products, as :func:`tpucfn.ops.attention.dot_product_attention`
casts ``probs``. Scores, softmax statistics, LSE, ``delta``, the
exponentials and every accumulator are float32. A float32 caller
computes in float32 throughout.

Row statistics stay two-dimensional from scratch to use: m, l, LSE and
``delta`` ride lane-replicated as (block_q, 128) where queries are rows,
and sublane-replicated as (8, block_q) in the key/value backward, which
forms its scores transposed (k qᵀ) so that neither of its accumulations
transposes a score tile. 1-D vectors don't tile VMEM.

Causal masking takes global ``q_offset``/``k_offset`` so the same kernel
serves full attention and one ring-attention hop (SURVEY.md §2.3 "Ring
attention"). ``segment_ids`` adds packed-sequence (block-diagonal)
masking — the TPU-idiomatic form of a dense mask, laid out the way the
hardware wants it (ids of the row side broadcast across lanes, of the
column side across sublanes). GQA never materializes repeated KV: the
forward reads each KV head once via BlockSpec index maps, and the
backward dK/dV kernel loops the query-head group as an extra grid
dimension, accumulating into the shared KV-head gradient.

Arbitrary sequence lengths are handled by padding to the block size in
the wrapper (padded keys are masked via ``kv_len``; padded query rows
are sliced off — their backward contributions are provably zero because
``do`` is zero there). Block sizes are parameters: per call, else
TPUCFN_FLASH_BLOCK_Q/_K, else the measured table of
:mod:`tpucfn.kernels.flash_autotune`, else 128/128 (:func:`_choose_blocks`).

**Values narrower than keys.** ``v`` (and so ``o``, ``do``, ``dv``) may
have another head size than ``q`` and ``k`` (latent attention: keys of 192,
values of 128): every array has block specs and accumulators of its own
width, and the scale is ``scale`` or, by default, the keys' ``d ** -0.5``
(a model that states its own multiplier passes it: the kernels multiply the
scores by whatever they are given). A key size past the lanes that is no
multiple of them (192 = 128 + 64) goes to the MXU as it is, one 192-wide
product: on a v5e the 128 and the 64 as two products into one score read
the same, and keys zero-padded to 256 make the three kernels 10% faster
(the re-tiling of a half-filled lane tile goes) but cost more than that in
the padding of q and k and the slicing of their gradients around every
call (PERF.md, PR 31).

Layout: (B, H, S, D) inside the kernels — S×D trailing tiles are what
the MXU wants. The public wrapper takes the framework-standard
(B, S, H, D).

Interpret mode (``interpret=True``) runs the same kernels on CPU for CI;
tests compare against :func:`tpucfn.ops.attention.dot_product_attention`.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # mask value; finite so max/exp never see nan-producing -inf
LANES = 128      # lane width (TPU tiling)
SUBLANES = 8     # f32 sublane tile

_NN = (((1,), (0,)), ((), ()))  # a · b
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ: contract the trailing axis of both

# Names of the forward's results that the backward needs, ``o`` and the
# compact (B, H, SQ) LSE: a ``jax.checkpoint`` policy that saves them
# (``models.llama.remat_policy("full")``) keeps the forward kernel out of
# the rematerialised pass.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
# The name a model gives a selection of keys (the words, and whatever was
# read with them) so that the same policy keeps the selection's forward
# out of the rematerialised pass too.
FLASH_SELECT = "flash_select"


def _block_and_pad(s: int, target: int) -> tuple[int, int]:
    """(block, padded_s): block ≤ target, multiple of SUBLANES, tiling the
    padded length. Sequences shorter than the target become one block."""
    if s >= target:
        block = target
    else:
        block = -(-s // SUBLANES) * SUBLANES  # round up to sublane tile
    padded = -(-s // block) * block
    return block, padded


def _pad_seq(x: jax.Array, s_padded: int, axis: int) -> jax.Array:
    pad = s_padded - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@dataclasses.dataclass(frozen=True)
class _Grid:
    """What a block's place on the (query block, key block) grid decides.

    The predicates are plain arithmetic, so they read ``program_id``
    scalars inside a kernel, grid indices inside an index map and numpy
    arrays in :meth:`count` alike. One that the static facts settle
    comes back as a Python bool."""

    causal: bool
    block_q: int
    block_k: int
    q_offset: int
    k_offset: int
    kv_len: int   # true key count
    sk_pad: int   # padded key count: keys from kv_len on are padding
    have_segs: bool
    # a band: query t sees key s only where t - s < window (causal only)
    window: int | None = None
    # a selection of keys carried as data: packed words of :func:`pack`
    select: bool = False

    @property
    def name(self) -> str:
        """The kernels' name prefix, so that a trace tells the three
        attentions apart."""
        return "swa" if self.window else "dsa" if self.select else "flash"

    @property
    def shift(self) -> int:
        """Global position of local query 0 less that of local key 0."""
        return self.q_offset - self.k_offset

    @property
    def padded(self) -> bool:
        return self.kv_len < self.sk_pad

    @property
    def guard(self) -> bool:
        """Whether a row can be fully masked in every block it has seen.
        A plain-causal row keeps local key 0, in the first block it
        computes, and padded keys are never the first."""
        return (self.have_segs or self.select or bool(self.window)
                or (self.causal and self.shift < 0))

    def needed(self, qi, ki):
        """The block holds a pair that causality (and the band) keeps."""
        if not self.causal:
            return True
        below = self.shift + (qi + 1) * self.block_q - 1 >= ki * self.block_k
        if self.window:
            below = below & ((ki + 1) * self.block_k - 1 >= self.shift
                             + qi * self.block_q - (self.window - 1))
        return below

    def interior(self, qi, ki):
        """No pair of the block is masked."""
        if self.have_segs or self.select:
            return False
        inside = True
        if self.causal:
            inside = ((ki + 1) * self.block_k - 1
                      <= self.shift + qi * self.block_q)
        if self.window:
            inside = inside & (ki * self.block_k >= self.shift + (qi + 1)
                               * self.block_q - 1 - (self.window - 1))
        if self.padded:
            inside = inside & ((ki + 1) * self.block_k <= self.kv_len)
        return inside

    # A band walks only the blocks it can touch: the key (query) axis of
    # the grid counts steps from the first block a query (key) block
    # needs, so a 513-key window at S 16,384 makes 2-3 steps a row of
    # blocks and not 32.  Without a band a step is the block itself.

    def k_steps(self, nk: int) -> int:
        if not self.window:
            return nk
        return min(nk, (self.block_q + self.window - 2) // self.block_k + 2)

    def q_steps(self, nq: int) -> int:
        if not self.window:
            return nq
        return min(nq, (self.block_k + self.window - 2) // self.block_q + 2)

    def key_block(self, qi, j):
        """The key block of step ``j`` in query block ``qi``'s row."""
        if not self.window:
            return j
        first = jnp.maximum(self.shift + qi * self.block_q
                            - (self.window - 1), 0) // self.block_k
        return first + j

    def query_block(self, ki, j):
        """The query block of step ``j`` in key block ``ki``'s column."""
        if not self.window:
            return j
        return jnp.maximum(ki * self.block_k - self.shift, 0) // self.block_q + j

    def last_needed_k(self, qi, ki):
        """``ki``, held at the last key block query block ``qi`` needs."""
        if not self.causal:
            return ki
        reach = jnp.maximum(self.shift + (qi + 1) * self.block_q - 1, 0)
        return jnp.minimum(ki, reach // self.block_k)

    def first_needed_q(self, qi, ki, nq: int):
        """``qi``, held at the first query block key block ``ki`` needs
        (and, in a band, at the last one)."""
        if not self.causal:
            return qi
        first = jnp.maximum(ki * self.block_k - self.shift, 0) // self.block_q
        held = jnp.maximum(qi, jnp.minimum(first, nq - 1))
        if self.window:
            last = (ki * self.block_k + self.block_k + self.window - 2
                    - self.shift) // self.block_q
            held = jnp.minimum(held, jnp.clip(last, 0, nq - 1))
        return held

    def keep(self, qi, ki, q_axis: int, q_seg, kv_seg, q_rows=None,
             chosen=None):
        """The pairs of an edge block that survive, its queries (or the
        slice ``q_rows`` of them) along ``q_axis`` of the score tile and
        its keys along the other: only the parts of the mask that can be
        live.  ``chosen`` is the selection's tile, in the score tile's
        orientation."""
        q_rows = q_rows or slice(0, self.block_q)
        shape = [self.block_k, self.block_k]
        shape[q_axis] = q_rows.stop - q_rows.start
        kpos = ki * self.block_k + lax.broadcasted_iota(
            jnp.int32, tuple(shape), 1 - q_axis)
        parts = []
        if self.padded:
            parts.append(kpos < self.kv_len)  # padded keys never attend
        if self.causal:
            qpos = (self.shift + qi * self.block_q + q_rows.start
                    + lax.broadcasted_iota(jnp.int32, tuple(shape), q_axis))
            parts.append(qpos >= kpos)
            if self.window:
                parts.append(qpos - kpos < self.window)
        if q_seg is not None:
            parts.append(q_seg == kv_seg)
        if chosen is not None:
            parts.append(chosen)
        return functools.reduce(jnp.logical_and, parts)

    def count(self, sq_pad: int) -> dict[str, int]:
        """Grid steps of one head by class, for these blocks."""
        qi, ki = np.meshgrid(np.arange(sq_pad // self.block_q),
                             np.arange(self.sk_pad // self.block_k),
                             indexing="ij")
        needed = np.broadcast_to(self.needed(qi, ki), qi.shape)
        interior = np.broadcast_to(self.interior(qi, ki), qi.shape)
        return {"interior": int(interior.sum()),
                "edge": int((needed & ~interior).sum()),
                "skipped": int((~needed).sum())}


# A selection of keys travels as packed words: bit ``j`` of lane ``l`` in
# word-row ``g`` of a query's row is key ``g * GROUP + j * LANES + l``, so a
# key block of whole lane tiles reads one (rows, LANES) tile of words and
# unpacks it with a shift a lane tile (:func:`pack` writes them).
WORD = 32
GROUP = WORD * LANES


def pack(mask: jax.Array) -> jax.Array:
    """(B, SQ, SK) bool → (B, ceil(SK / GROUP), SQ, LANES) int32 words."""
    b, sq, sk = mask.shape
    g = -(-sk // GROUP)
    m = jnp.pad(mask, ((0, 0), (0, 0), (0, g * GROUP - sk)))
    m = m.reshape(b, sq, g, WORD, LANES).astype(jnp.uint32)
    words = jnp.sum(m << jnp.arange(WORD, dtype=jnp.uint32)[:, None], axis=3)
    return lax.bitcast_convert_type(words, jnp.int32).transpose(0, 2, 1, 3)


def unpack(words: jax.Array, sk: int) -> jax.Array:
    """:func:`pack`'s inverse: (B, G, SQ, LANES) int32 → (B, SQ, SK) bool."""
    b, g, sq, _ = words.shape
    bits = (words.transpose(0, 2, 1, 3)[:, :, :, None, :]
            >> jnp.arange(WORD)[:, None]) & 1
    return bits.reshape(b, sq, g * GROUP)[:, :, :sk].astype(bool)


def _chosen(grid: _Grid, words, ki, transposed: bool = False):
    """The selection over key block ``ki`` from the (rows, LANES) words of
    its rows, as the score tile lies: (rows, BK), or (BK, rows) where keys
    are the tile's rows."""
    first = ki * grid.block_k % GROUP // LANES
    tile = jnp.concatenate(
        [jnp.right_shift(words, first + c) & 1
         for c in range(grid.block_k // LANES)], axis=1)
    return (tile.T if transposed else tile) != 0


def _by_class(grid: _Grid, qi, ki, step, inside=True) -> None:
    """Run ``step(masked)`` as the block's class asks: not at all, without
    the mask, or with it. Each body is traced once. ``inside``: the step's
    block lies on the array (a band's last steps of a row can run past
    it)."""
    needed, interior = grid.needed(qi, ki), grid.interior(qi, ki)
    if inside is not True:
        needed, interior = needed & inside, interior & inside
    if interior is True:  # full attention with nothing padded
        step(False)
    elif interior is False:  # segment ids: every computed block is an edge
        pl.when(needed)(lambda: step(True))
    else:
        pl.when(interior)(lambda: step(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))(
            lambda: step(True))


def _across(x, width: int):
    """(rows, LANES) lane-replicated → (rows, width)."""
    if width % LANES == 0:
        return x if width == LANES else jnp.tile(x, (1, width // LANES))
    if width < LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _down(x, height: int):
    """(SUBLANES, cols) sublane-replicated → (height, cols)."""
    return x if height == SUBLANES else jnp.tile(x, (height // SUBLANES, 1))


_ROWS = 256  # query rows of a forward block worked off at a time


def _row_chunks(rows: int) -> list[slice]:
    """The forward works a block's score tile off ``_ROWS`` query rows at a
    time, each chunk from its scores to its accumulation: a chunk's
    scores stay near the registers and one chunk's products overlap the
    next one's vector work (a 1,024 × 1,024 step 4.4 ms a call against
    5.1 whole, 5.1 at 128 rows; the backward kernels, which reduce
    nothing along a row, read the same either way)."""
    size = _ROWS if rows % _ROWS == 0 else rows
    return [slice(r, r + size) for r in range(0, rows, size)]


def _product(a, b, dims=_NN):
    """a · b (or a · bᵀ under ``_NT``) in the operands' own dtype,
    accumulated in float32."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _probabilities(s, offset, guarded: bool):
    """exp(s - offset); where a row can be fully masked its offset is
    NEG_INF too, so masked entries are zeroed explicitly."""
    p = jnp.exp(s - offset)
    if guarded:
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
    return p


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _extra(grid: _Grid, rest):
    """(segment id refs or Nones, selection ref or None, the rest)."""
    segs = (None, None)
    if grid.have_segs:
        segs, rest = rest[:2], rest[2:]
    sel = None
    if grid.select:
        sel, rest = rest[0], rest[1:]
    return segs, sel, rest


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, grid: _Grid, scale):
    (qseg_ref, kseg_ref), sel_ref, rest = _extra(grid, rest)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = grid.key_block(qi, j)
    d = acc_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        k, v = k_ref[0, 0], v_ref[0, 0]                # (BK, D), (BK, DV)
        for rows in _row_chunks(grid.block_q):
            s = _product(q_ref[0, 0, rows, :], k, _NT) * scale  # (rows, BK)
            if masked:
                q_seg = kv_seg = chosen = None
                if grid.have_segs:
                    q_seg = qseg_ref[0, rows, :1]  # (rows, 1) lane-replicated
                    kv_seg = kseg_ref[0, :1, :]    # (1, BK) sublane-replicated
                if grid.select:
                    chosen = _chosen(grid, sel_ref[0, 0, rows, :], ki)
                s = jnp.where(grid.keep(qi, ki, 0, q_seg, kv_seg, rows, chosen),
                              s, NEG_INF)
            m_prev = m_ref[rows, :]                         # (rows, LANES)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = _probabilities(s, _across(m_next, grid.block_k),
                               masked and grid.guard)
            # m_prev == m_next == NEG_INF (nothing live yet) gives alpha 1
            # over an l and an acc that are still zero.
            alpha = jnp.exp(m_prev - m_next)
            l_ref[rows, :] = alpha * l_ref[rows, :] + jnp.sum(
                p, axis=1, keepdims=True)
            acc_ref[rows, :] = acc_ref[rows, :] * _across(alpha, d) + _product(
                p.astype(v.dtype), v)
            m_ref[rows, :] = m_next

    _by_class(grid, qi, ki, step, ki * grid.block_k < grid.sk_pad
              if grid.window else True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[...]
        live = l > 0  # a fully masked row gives zeros and lse = NEG_INF
        safe_l = jnp.where(live, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / _across(safe_l, d)).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(live, m_ref[...] + jnp.log(safe_l), NEG_INF)


def _flash_fwd(q, k, v, q_seg, kv_seg, select=None, *, causal, q_offset,
               k_offset, kv_len, block_sizes, interpret, scale, window=None):
    """q: (B, H, SQ, D); k: (B, HKV, SK, D); v: (B, HKV, SK, DV) →
    (o[B,H,SQ,DV], lse[B,H,SQ,LANES]).

    SQ/SK already padded to block multiples; kv_len = true key count."""
    b, h, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = h // hkv
    block_q, block_k = block_sizes
    have_segs = q_seg is not None
    grid = _Grid(causal, block_q, block_k, q_offset, k_offset, kv_len, sk,
                 have_segs, window, select is not None)

    qspec, kspec, vspec, ospec, qrow = _query_major_specs(grid, rep, d, dv)
    in_specs = [qspec, kspec, vspec]
    args = [q, k, v]
    if have_segs:
        seg_specs, seg_args = _seg_operands(grid, q_seg, kv_seg)
        in_specs += seg_specs
        args += seg_args
    if grid.select:
        in_specs.append(_select_spec(grid))
        args.append(select)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, grid=grid, scale=scale),
        grid=(b, h, sq // block_q, grid.k_steps(sk // block_k)),
        in_specs=in_specs,
        out_specs=[ospec, qrow],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=f"{grid.name}_fwd",
    )(*args)
    return o, lse


def _lanes(x):
    """(..., n) → (..., n, LANES), each value replicated across lanes: the
    layout of a per-row quantity where its axis is the tile's rows."""
    return jnp.broadcast_to(x[..., None], (*x.shape, LANES))


def _sublanes(x):
    """(..., n) → (..., SUBLANES, n), replicated across sublanes: the
    layout of a per-row quantity where its axis is the tile's columns."""
    return jnp.broadcast_to(x[..., None, :],
                            (*x.shape[:-1], SUBLANES, x.shape[-1]))


def _query_major_specs(grid: _Grid, rep: int, d: int, dv: int):
    """Block specs on the grid (b, h, qi, ki) of the forward and the query
    backward: a q-shaped array, a k-shaped and a v-shaped one (GQA: head
    ``hi // rep``, its block held at the last one needed), an o-shaped one
    (a query's rows at the values' width) and a (SQ, LANES) row quantity."""
    def query(width):
        return pl.BlockSpec((1, 1, grid.block_q, width),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    def key(width):
        return pl.BlockSpec(
            (1, 1, grid.block_k, width), lambda bi, hi, qi, j: (
                bi, hi // rep, grid.last_needed_k(qi, grid.key_block(qi, j)),
                0))

    return query(d), key(d), key(dv), query(dv), query(LANES)


def _select_spec(grid: _Grid):
    """The selection's words of a query block's rows over the word-row that
    holds the key block, on the grid (b, h, qi, j) of the forward and the
    query backward."""
    def words(bi, hi, qi, j):
        ki = grid.last_needed_k(qi, grid.key_block(qi, j))
        return (bi, ki * grid.block_k // GROUP, qi, 0)

    return pl.BlockSpec((1, 1, grid.block_q, LANES), words)


def _seg_operands(grid: _Grid, q_seg, kv_seg):
    """Specs and arrays of the segment ids where queries are the score
    tile's rows (grid (b, h, qi, ki)); key ids follow k's clamp."""
    specs = [
        pl.BlockSpec((1, grid.block_q, LANES),
                     lambda bi, hi, qi, ki: (bi, qi, 0)),
        pl.BlockSpec((1, SUBLANES, grid.block_k),
                     lambda bi, hi, qi, j: (
                         bi, 0, grid.last_needed_k(qi, grid.key_block(qi, j)))),
    ]
    return specs, [_lanes(q_seg), _sublanes(kv_seg)]


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               grid: _Grid, scale):
    (qseg_ref, kseg_ref), sel_ref, (dq_ref, dq_acc) = _extra(grid, rest)
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = grid.key_block(qi, j)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked: bool):
        k, v = k_ref[0, 0], v_ref[0, 0]
        s = _product(q_ref[0, 0], k, _NT) * scale            # (BQ, BK)
        if masked:
            q_seg = kv_seg = chosen = None
            if grid.have_segs:
                q_seg = qseg_ref[0, :, :1]
                kv_seg = kseg_ref[0, :1, :]
            if grid.select:
                chosen = _chosen(grid, sel_ref[0, 0], ki)
            s = jnp.where(grid.keep(qi, ki, 0, q_seg, kv_seg, None, chosen),
                          s, NEG_INF)
        p = _probabilities(s, _across(lse_ref[0, 0], grid.block_k),
                           masked and grid.guard)
        dp = _product(do_ref[0, 0], v, _NT)
        ds = p * (dp - _across(delta_ref[0, 0], grid.block_k))
        dq_acc[...] += _product(ds.astype(k.dtype), k)

    _by_class(grid, qi, ki, step, ki * grid.block_k < grid.sk_pad
              if grid.window else True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                grid: _Grid, scale, nq: int):
    """Scores are formed transposed, keys as rows (k qᵀ), so that both
    accumulations are plain products; ``lse`` and ``delta`` arrive as
    (SUBLANES, BQ) rows.

    Grid: (b, hkv, ki, rep, qi) — the query-head group is a grid
    dimension INSIDE the KV-block dimension, so for each KV block the
    scratch accumulates over every (rep, qi) before moving on; GQA
    accumulates straight into the shared KV-head gradient without ever
    materializing repeated K/V (the VERDICT r1 "kills the GQA memory
    advantage" fix)."""
    (qseg_ref, kseg_ref), sel_ref, rest = _extra(grid, rest)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    ki, ri, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    qi = grid.query_block(ki, j)

    @pl.when((ri == 0) & (j == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q, do = q_ref[0, 0], do_ref[0, 0]                   # (BQ, D)
        st = _product(k_ref[0, 0], q, _NT) * scale           # (BK, BQ)
        if masked:
            q_seg = kv_seg = chosen = None
            if grid.have_segs:
                q_seg = qseg_ref[0, :1, :]   # (1, BQ) sublane-replicated
                kv_seg = kseg_ref[0, :, :1]  # (BK, 1) lane-replicated
            if grid.select:
                chosen = _chosen(grid, sel_ref[0, 0], ki, transposed=True)
            st = jnp.where(grid.keep(qi, ki, 1, q_seg, kv_seg, None, chosen),
                           st, NEG_INF)
        pt = _probabilities(st, _down(lse_ref[0, 0], grid.block_k),
                            masked and grid.guard)
        dv_acc[...] += _product(pt.astype(do.dtype), do)
        dpt = _product(v_ref[0, 0], do, _NT)
        dst = pt * (dpt - _down(delta_ref[0, 0], grid.block_k))
        dk_acc[...] += _product(dst.astype(q.dtype), q)

    _by_class(grid, qi, ki, step, qi < nq if grid.window else True)

    @pl.when((ri == pl.num_programs(3) - 1)
             & (j == pl.num_programs(4) - 1))
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_dq(q, k, v, do, lse, delta, q_seg, kv_seg, select=None, *,
              grid: _Grid, interpret, scale):
    """dQ: grid (b, h, qi, ki), KV blocks stream per query block. ``lse``
    and ``delta``: (B, H, SQ, LANES), lane-replicated."""
    b, h, sq, d = q.shape
    rep, sk = h // k.shape[1], k.shape[2]
    block_q, block_k = grid.block_q, grid.block_k
    qspec, kspec, vspec, ospec, qrow = _query_major_specs(
        grid, rep, d, v.shape[-1])
    in_specs = [qspec, kspec, vspec, ospec, qrow, qrow]
    args = [q, k, v, do, lse, delta]
    if grid.have_segs:
        seg_specs, seg_args = _seg_operands(grid, q_seg, kv_seg)
        in_specs += seg_specs
        args += seg_args
    if grid.select:
        in_specs.append(_select_spec(grid))
        args.append(select)
    return pl.pallas_call(
        functools.partial(_dq_kernel, grid=grid, scale=scale),
        grid=(b, h, sq // block_q, grid.k_steps(sk // block_k)),
        in_specs=in_specs,
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=f"{grid.name}_dq",
    )(*args)[0]


def _flash_dkv(q, k, v, do, lse, delta, q_seg, kv_seg, select=None, *,
               grid: _Grid, interpret, scale):
    """dK/dV: grid (b, hkv, ki, rep, qi) — for each KV block, accumulate
    over the query-head group and the query blocks; the KV-head block
    stays resident for its whole accumulation. ``lse`` and ``delta``:
    (B, H, SUBLANES, SQ), sublane-replicated."""
    b, h, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = h // hkv
    block_q, block_k = grid.block_q, grid.block_k
    nq = sq // block_q

    def held_q(ki, j):
        return grid.first_needed_q(grid.query_block(ki, j), ki, nq)

    def q_map(bi, hk, ki, ri, j):
        return (bi, hk * rep + ri, held_q(ki, j), 0)

    def q_row_map(bi, hk, ki, ri, j):
        return (bi, hk * rep + ri, 0, held_q(ki, j))

    def k_map(bi, hk, ki, ri, j):
        return (bi, hk, ki, 0)

    qspec = pl.BlockSpec((1, 1, block_q, d), q_map)
    dospec = pl.BlockSpec((1, 1, block_q, dv), q_map)
    kspec = pl.BlockSpec((1, 1, block_k, d), k_map)
    vspec = pl.BlockSpec((1, 1, block_k, dv), k_map)
    qrow = pl.BlockSpec((1, 1, SUBLANES, block_q), q_row_map)
    in_specs = [qspec, kspec, vspec, dospec, qrow, qrow]
    args = [q, k, v, do, lse, delta]
    if grid.have_segs:
        in_specs += [
            pl.BlockSpec((1, SUBLANES, block_q),
                         lambda bi, hk, ki, ri, j: (bi, 0, held_q(ki, j))),
            pl.BlockSpec((1, block_k, LANES),
                         lambda bi, hk, ki, ri, j: (bi, ki, 0)),
        ]
        args += [_sublanes(q_seg), _lanes(kv_seg)]
    if grid.select:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q, LANES), lambda bi, hk, ki, ri, j: (
                bi, ki * block_k // GROUP, held_q(ki, j), 0)))
        args.append(select)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, grid=grid, scale=scale, nq=nq),
        grid=(b, hkv, sk // block_k, rep, grid.q_steps(nq)),
        in_specs=in_specs,
        out_specs=[kspec, vspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name=f"{grid.name}_dkv",
    )(*args)


def _flash_bwd(q, k, v, o, lse, do, q_seg, kv_seg, select=None, *, causal,
               q_offset, k_offset, kv_len, block_sizes, interpret, scale,
               dlse=None, window=None):
    """q: (B, H, SQ, D); k: (B, HKV, SK, D); v: (B, HKV, SK, DV); o/do:
    (B, H, SQ, DV) — KV stays un-repeated; ``lse``: (B, H, SQ) float32,
    one lane of what the forward wrote. ``dlse`` (B, H, SQ) is the
    LSE-output cotangent for the with-lse variant (ring hops); None when
    only O was consumed."""
    grid = _Grid(causal, *block_sizes, q_offset, k_offset, kv_len,
                 k.shape[2], q_seg is not None, window, select is not None)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # When LSE is itself an output (ring-hop merge weights), its
        # cotangent flows through d lse / d s = p: ds = p (dp - delta + dlse).
        delta = delta - dlse.astype(jnp.float32)
    dq = _flash_dq(q, k, v, do, _lanes(lse), _lanes(delta), q_seg, kv_seg,
                   select, grid=grid, interpret=interpret, scale=scale)
    dk, dv = _flash_dkv(q, k, v, do, _sublanes(lse), _sublanes(delta),
                        q_seg, kv_seg, select, grid=grid, interpret=interpret,
                        scale=scale)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public API with custom VJP
# --------------------------------------------------------------------------


def _make_flash(causal, q_offset, k_offset, kv_len, block_sizes, interpret,
                scale, window=None, with_lse=False):
    """custom_vjp closure over the static config; segment ids and a
    selection's words ride as residual (nondiff) operands. The residuals
    ``o`` and the compact LSE carry the names :data:`FLASH_OUT` and
    :data:`FLASH_LSE`, so that a remat policy can save them instead of
    running the forward again. ``with_lse`` hands the compact LSE out
    beside ``o`` as a reading: its cotangent is dropped, so the caller
    holds it under ``stop_gradient``."""
    static = dict(causal=causal, q_offset=q_offset, k_offset=k_offset,
                  kv_len=kv_len, block_sizes=block_sizes, interpret=interpret,
                  scale=scale, window=window)

    @jax.custom_vjp
    def run(q, k, v, q_seg, kv_seg, select):
        o, lse = _flash_fwd(q, k, v, q_seg, kv_seg, select, **static)
        return (o, lse[..., 0]) if with_lse else o

    def fwd(q, k, v, q_seg, kv_seg, select):
        o, lse = _flash_fwd(q, k, v, q_seg, kv_seg, select, **static)
        o = checkpoint_name(o, FLASH_OUT)
        lse = checkpoint_name(lse[..., 0], FLASH_LSE)
        return ((o, lse) if with_lse else o), (
            q, k, v, q_seg, kv_seg, select, o, lse)

    def bwd(res, do):
        q, k, v, q_seg, kv_seg, select, o, lse = res
        if with_lse:
            do = do[0]
        dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, q_seg, kv_seg, select,
                                **static)
        zeros = [None if x is None else np.zeros(x.shape, jax.dtypes.float0)
                 for x in (q_seg, kv_seg, select)]
        return (dq, dk.astype(k.dtype), dv.astype(v.dtype), *zeros)

    run.defvjp(fwd, bwd)
    return run


def _make_flash_with_lse(causal, q_offset, k_offset, kv_len, block_sizes,
                         interpret, scale):
    """Like _make_flash but LSE is a first-class differentiable output
    (the ring-hop merge consumes it): the backward takes (do, dlse) and
    routes dlse through the kernels' p·dlse term."""
    static = dict(causal=causal, q_offset=q_offset, k_offset=k_offset,
                  kv_len=kv_len, block_sizes=block_sizes, interpret=interpret,
                  scale=scale)

    def _fwd_pair(q, k, v):
        o, lse_l = _flash_fwd(q, k, v, None, None, **static)
        return o, lse_l[..., 0]  # (B, H, SQ) float32

    @jax.custom_vjp
    def run(q, k, v):
        return _fwd_pair(q, k, v)

    def fwd(q, k, v):
        o, lse = _fwd_pair(q, k, v)
        return (o, lse), (q, k, v, o, lse)

    def bwd(res, cts):
        do, dlse = cts
        q, k, v, o, lse = res
        dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, None, None,
                                dlse=dlse, **static)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)

    run.defvjp(fwd, bwd)
    return run


def _prep_inputs(q, k, v, block_q, block_k, interpret, causal=True,
                 mask_kind=None):
    """Shared wrapper prologue: interpret default, block selection, and
    layout/pad of (B, S, H, D) inputs into kernel (B, H, S_pad, D)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sq, sk = q.shape[1], k.shape[1]
    if block_q is not None:
        block_q = _check_block(block_q, "block_q")
    if block_k is not None:
        block_k = _check_block(block_k, "block_k")
    if block_q is None or block_k is None:
        # Only consult env/tuned defaults when actually needed — a bad
        # cached entry must not break calls that pinned their blocks.
        bq0, bk0 = _choose_blocks(sq, q.shape[-1], q.dtype, causal,
                                  v.shape[-1], mask_kind)
    else:
        bq0 = bk0 = None
    blk_q, sq_pad = _block_and_pad(sq, block_q or bq0)
    blk_k, sk_pad = _block_and_pad(sk, block_k or bk0)
    qt = _pad_seq(jnp.swapaxes(q, 1, 2), sq_pad, 2)
    kt = _pad_seq(jnp.swapaxes(k, 1, 2), sk_pad, 2)
    vt = _pad_seq(jnp.swapaxes(v, 1, 2), sk_pad, 2)
    return qt, kt, vt, (blk_q, blk_k), (sq, sk, sq_pad, sk_pad), interpret


def flash_attention_with_lse(
    q: jax.Array,  # (B, SQ, H, D)
    k: jax.Array,  # (B, SK, HKV, D)
    v: jax.Array,  # (B, SK, HKV, DV); DV may differ from D
    *,
    causal: bool = True,
    q_offset: int = 0,
    k_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(out (B,SQ,H,DV), lse (B,SQ,H)) — the flash counterpart of
    :func:`tpucfn.ops.attention.dot_product_attention_with_lse`, for
    ring-attention hops (rows attending to nothing give lse = NEG_INF).
    Differentiable in both outputs."""
    qt, kt, vt, blocks, (sq, sk, _, _), interpret = _prep_inputs(
        q, k, v, block_q, block_k, interpret, causal)
    run = _make_flash_with_lse(causal, int(q_offset), int(k_offset), sk,
                               blocks, interpret, _scale(None, q))
    o, lse = run(qt, kt, vt)
    return (jnp.swapaxes(o[:, :, :sq], 1, 2),
            jnp.swapaxes(lse[:, :, :sq], 1, 2))


def _scale(scale: float | None, q: jax.Array) -> float:
    return q.shape[-1] ** -0.5 if scale is None else float(scale)


def _check_block(value: int, origin: str) -> int:
    """Block targets must be positive multiples of the sublane tile —
    anything else would surface later as a divide-by-zero or an opaque
    Mosaic lowering failure on TPU (ADVICE r2)."""
    try:
        as_int = int(value)
        if as_int != float(value):  # reject silent truncation (136.5 -> 136)
            raise ValueError
        value = as_int
    except (TypeError, ValueError) as e:
        raise ValueError(f"{origin} must be an integer, got {value!r}") from e
    if value <= 0 or value % SUBLANES:
        raise ValueError(
            f"{origin} must be a positive multiple of {SUBLANES}, got {value}")
    return value


def _choose_blocks(sq: int, d: int, dtype, causal: bool,
                   dv: int | None = None,
                   mask_kind: str | None = None) -> tuple[int, int]:
    """Default block selection when the caller passed none: env override
    (explicit experiment control) > the measured table (flash_autotune:
    the packaged rows of ``flash_tune_builtin.json`` under a user's own
    tunes) > 128/128 where nothing was measured. The pair is the only
    per-shape datum: what a block step does follows from the pair, the
    block's place on the grid and the arrays' dtype (module docstring),
    and the pair's optimum moves with it (fewer, larger blocks since the
    per-element work fell: a grid step's fixed cost weighs more)."""
    envq = os.environ.get("TPUCFN_FLASH_BLOCK_Q")
    envk = os.environ.get("TPUCFN_FLASH_BLOCK_K")
    if envq or envk:
        return (_check_block(envq or 128, "TPUCFN_FLASH_BLOCK_Q"),
                _check_block(envk or 128, "TPUCFN_FLASH_BLOCK_K"))
    from tpucfn.kernels import flash_autotune

    hit = flash_autotune.lookup(sq, d, dtype, causal, dv, mask_kind)
    if hit:
        return (_check_block(hit[0], "tuned block_q"),
                _check_block(hit[1], "tuned block_k"))
    return 128, 128


def flash_attention(
    q: jax.Array,  # (B, SQ, H, D) — framework-standard layout
    k: jax.Array,  # (B, SK, HKV, D)
    v: jax.Array,  # (B, SK, HKV, DV) → out (B, SQ, H, DV)
    *,
    causal: bool = True,
    mask: jax.Array | None = None,
    segment_ids: jax.Array | tuple[jax.Array, jax.Array] | None = None,
    q_offset: int = 0,
    k_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    scale: float | None = None,
    window: int | None = None,
    select: jax.Array | None = None,
    return_lse: bool = False,
) -> jax.Array:
    """Drop-in replacement for
    :func:`tpucfn.ops.attention.dot_product_attention`.

    ``scale`` multiplies the scores; None is the keys' ``D ** -0.5``.

    ``window``: causal attention over a band, query ``t`` seeing key ``s``
    only where ``t - s < window`` (kernels ``swa_*``); the grid walks the
    blocks the band touches. ``select``: the keys each query attends to,
    as :func:`pack`'s words (B, ceil(S / GROUP), S, LANES), within
    causality (kernels ``dsa_*``); blocks of whole lane tiles, no padding.
    ``return_lse``: also the rows' log-sum-exp (B, SQ, H), a reading held
    under ``stop_gradient``.

    ``segment_ids``: (B, S) int array (self-attention) or a
    ``(q_ids, kv_ids)`` pair — attention is masked across segment
    boundaries (packed-sequence training). Dense boolean masks are
    deliberately unsupported: segments + causal cover the LM families,
    and a dense mask forfeits the O(S·D) memory bound.
    """
    if mask is not None:
        raise NotImplementedError(
            "flash_attention supports causal/segment masking, a band and a "
            "packed selection only")
    if (window or select is not None) and (not causal or segment_ids
                                            is not None):
        raise NotImplementedError("a band or a selection is causal and "
                                  "takes no segment ids")
    mask_kind = (f"window{window}" if window else
                 "select" if select is not None else None)
    qt, kt, vt, blocks, (sq, sk, sq_pad, sk_pad), interpret = _prep_inputs(
        q, k, v, block_q, block_k, interpret, causal, mask_kind)
    if select is not None and (
            (sq, sk) != (sq_pad, sk_pad) or blocks[1] % LANES
            or GROUP % blocks[1] or blocks[0] % LANES
            or select.shape != (q.shape[0], -(-sk // GROUP), sq, LANES)):
        raise ValueError(
            f"a selection needs whole blocks of lane tiles that divide "
            f"{GROUP} keys and words of shape (B, G, S, {LANES}); blocks "
            f"{blocks} at S {sq}, words {select.shape}")

    q_seg = kv_seg = None
    if segment_ids is not None:
        q_seg, kv_seg = (segment_ids if isinstance(segment_ids, tuple)
                         else (segment_ids, segment_ids))
        # Padded positions (query AND key) get segment -1. Padded keys
        # are already excluded by kv_len; -1 on both sides keeps padded
        # query rows from sharing a segment with real id-0 tokens (they
        # end up fully masked -> zero rows, sliced off below). Note
        # -1 == -1 would let padded queries see padded keys, but kv_len
        # masks those keys first.
        q_seg = jnp.where(
            jnp.arange(sq_pad)[None, :] < sq,
            _pad_seq(q_seg.astype(jnp.int32), sq_pad, 1), -1)
        kv_seg = jnp.where(
            jnp.arange(sk_pad)[None, :] < sk,
            _pad_seq(kv_seg.astype(jnp.int32), sk_pad, 1), -1)

    run = _make_flash(causal, int(q_offset), int(k_offset), sk,
                      blocks, interpret, _scale(scale, q), window, return_lse)
    o = run(qt, kt, vt, q_seg, kv_seg, select)
    if return_lse:
        o, lse = o
        return (jnp.swapaxes(o[:, :, :sq], 1, 2),
                lax.stop_gradient(jnp.swapaxes(lse[:, :, :sq], 1, 2)))
    return jnp.swapaxes(o[:, :, :sq], 1, 2)
