"""Flash kernel vs the dense reference — forward and gradients, causal and
not, GQA, offsets. Runs in Pallas interpret mode on CPU (same kernel code
path the TPU compiles)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpucfn.kernels import flash_attention
from tpucfn.ops.attention import dot_product_attention


def _qkv(b=2, sq=64, sk=64, hq=4, hkv=4, d=32, seed=0):
    rng = jax.random.key(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (b, sq, hq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, sk, hkv, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, sk, hkv, d))
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_gqa():
    q, k, v = _qkv(hq=8, hkv=2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_offsets():
    q, k, v = _qkv(sq=32, sk=64)
    out = flash_attention(q, k, v, causal=True, q_offset=32, interpret=True)
    ref = dot_product_attention(q, k, v, causal=True, q_offset=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fully_masked_is_zero():
    q, k, v = _qkv(sq=32, sk=32)
    out = flash_attention(q, k, v, causal=True, k_offset=1000, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_non_128_blocks():
    # S=48 forces _pick_block to a non-power block that still tiles S
    q, k, v = _qkv(sq=48, sk=48, d=16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(sq=32, sk=32, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_gradients_gqa():
    q, k, v = _qkv(sq=32, sk=32, hq=4, hkv=2, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_forward_close():
    q, k, v = _qkv()
    out = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), causal=True, interpret=True)
    ref = dot_product_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2)


# ---- round-2 additions: segments, padding, blocks, GQA-unrepeated bwd ----


def _seg_mask(q_ids, kv_ids):
    """(B,Sq),(B,Sk) -> broadcastable boolean mask (B,1,Sq,Sk)."""
    return (q_ids[:, :, None] == kv_ids[:, None, :])[:, None]


def test_segment_ids_forward_matches_masked_dense():
    rs = np.random.RandomState(0)
    b, s, h, d = 2, 64, 4, 32
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    # two packed documents per row
    segs = jnp.asarray(np.concatenate(
        [np.zeros((b, 24), np.int32), np.ones((b, s - 24), np.int32)], 1))
    out = flash_attention(q, k, v, causal=True, segment_ids=segs,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=True,
                                mask=_seg_mask(segs, segs))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_segment_ids_gradients_match_masked_dense():
    rs = np.random.RandomState(1)
    b, s, h, d = 1, 32, 2, 16
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    segs = jnp.asarray((np.arange(s) >= 20).astype(np.int32))[None].repeat(b, 0)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       segment_ids=segs, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, causal=True, mask=_seg_mask(segs, segs)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("s", [100, 57, 130])
def test_odd_sequence_lengths_pad_and_match(s):
    """Non-tile-aligned S works via pad+mask (ADVICE r1: un-padded odd
    blocks would mis-tile on real TPU)."""
    rs = np.random.RandomState(2)
    b, h, d = 1, 2, 16
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    for causal in (True, False):
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"causal={causal}")

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4)


def test_block_size_override_matches():
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 256, 2, 32).astype(np.float32))
    k, v = q + 1.0, q - 1.0
    base = flash_attention(q, k, v, causal=True, interpret=True)
    for bq, bk in [(64, 128), (128, 64), (256, 256)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-5, err_msg=f"blocks {bq}x{bk}")


@pytest.mark.parametrize("blocks", [(32, 32), (None, None)])
def test_gqa_backward_without_kv_repeat(blocks):
    """dK/dV accumulate over the query-head group inside the kernel;
    grads must equal the dense GQA reference. The (32, 32) case forces
    MULTIPLE KV blocks per head group — the configuration where a wrong
    grid ordering (rep outside ki) corrupts the shared accumulator."""
    rs = np.random.RandomState(4)
    b, s, d = 1, 160, 16  # 160 also exercises the padding path
    bq, bk = blocks
    q = jnp.asarray(rs.randn(b, s, 8, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, 2, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, 2, d).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=bq,
                                       block_k=bk, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gd, "qkv"):
        assert a.shape == bb.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name} mismatch blocks={blocks}")


def test_flash_with_lse_matches_dense_and_dlse_grads():
    """LSE is a differentiable output (ring-hop merges consume it): a
    loss that uses BOTH o and lse must match the dense reference grads."""
    from tpucfn.kernels import flash_attention_with_lse
    from tpucfn.ops.attention import dot_product_attention_with_lse

    rs = np.random.RandomState(5)
    b, s, h, d = 1, 48, 2, 16
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))

    of, lf = flash_attention_with_lse(q, k, v, causal=True, interpret=True)
    od, ld = dot_product_attention_with_lse(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(of), np.asarray(od), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld), atol=2e-5)

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                          interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        o, lse = dot_product_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_gqa_with_segments_combined_gradients():
    """GQA (rep grid dim) and segment masking together in the dK/dV
    kernel — each is covered alone above; this pins the combination."""
    rs = np.random.RandomState(6)
    b, s, d = 1, 96, 16
    q = jnp.asarray(rs.randn(b, s, 8, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, 2, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, 2, d).astype(np.float32))
    segs = jnp.asarray((np.arange(s) >= 40).astype(np.int32))[None].repeat(b, 0)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, segment_ids=segs,
                                       block_q=32, block_k=32,
                                       interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, causal=True, mask=_seg_mask(segs, segs)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_noncausal_unet_shapes():
    """The UNet dispatch shapes: non-causal, D=40/160 (non-lane-multiple
    head dims), and 77-key cross attention — all must match dense."""
    rs = np.random.RandomState(0)
    for d, s_kv in [(40, None), (40, 77), (160, 77)]:
        q = jnp.asarray(rs.randn(2, 256, 8, d), jnp.float32)
        kv_s = 256 if s_kv is None else s_kv
        k = jnp.asarray(rs.randn(2, kv_s, 8, d), jnp.float32)
        v = jnp.asarray(rs.randn(2, kv_s, 8, d), jnp.float32)
        o = flash_attention(q, k, v, causal=False)
        ref = dot_product_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-6, err_msg=f"d={d} s_kv={s_kv}")


def test_flash_property_sweep_random_shapes_vs_dense():
    """Property sweep: random (S, Skv, H, Hkv, D, causal, segments,
    offsets) configurations must all match dense numerics — the kernel's
    masking/padding corners beyond the hand-picked cases."""
    rs = np.random.RandomState(42)
    for trial in range(12):
        d = int(rs.choice([32, 40, 64, 128]))
        hkv = int(rs.choice([1, 2, 4]))
        h = hkv * int(rs.choice([1, 2, 4]))
        causal = bool(rs.rand() < 0.5)
        sq = int(rs.randint(3, 70))
        skv = sq if causal else int(rs.randint(3, 70))
        q = jnp.asarray(rs.randn(2, sq, h, d), jnp.float32)
        k = jnp.asarray(rs.randn(2, skv, hkv, d), jnp.float32)
        v = jnp.asarray(rs.randn(2, skv, hkv, d), jnp.float32)

        seg = None
        kw = {}
        if causal and rs.rand() < 0.5 and sq == skv:
            # random packed segments: sorted ids incl. some padding (-1)
            ids = np.sort(rs.randint(0, 3, (2, sq))).astype(np.int32)
            seg = jnp.asarray(ids)
            kw["segment_ids"] = seg
        out = flash_attention(q, k, v, causal=causal, **kw)
        if seg is not None:
            mask = (seg[:, None, :, None] == seg[:, None, None, :])
            ref = dot_product_attention(q, k, v, causal=causal, mask=mask)
        else:
            ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-6,
            err_msg=f"trial={trial} sq={sq} skv={skv} h={h}/{hkv} d={d} "
                    f"causal={causal} seg={seg is not None}")


# ---- PR 30: every block class, both orientations, the dtype rule ----


def _grads(fn, q, k, v, w):
    """Gradients of sum(fn(q, k, v) * w): a cotangent that differs by row."""
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


# (id, S, q heads, kv heads, D, kwargs of flash_attention, segment ids?)
BLOCK_CLASS_CASES = [
    # interior, edge and skipped blocks with the query block the taller ...
    ("bq_gt_bk", 256, 4, 2, 16, dict(block_q=128, block_k=32), False),
    # ... and the wider: several query blocks end inside one key block
    ("bk_gt_bq", 256, 4, 2, 16, dict(block_q=32, block_k=128), False),
    # 200 keys padded to 256: the last key block is an edge for its padding
    ("padded_last_key_block", 200, 2, 2, 16, dict(block_q=64, block_k=64),
     False),
    # every block computed, none masked but the padded one
    ("noncausal_padded", 200, 2, 1, 16,
     dict(causal=False, block_q=64, block_k=64), False),
    # no mask anywhere: one body, no branch
    ("noncausal_all_interior", 192, 2, 1, 16,
     dict(causal=False, block_q=64, block_k=32), False),
    # segment ids with GQA: every computed block is an edge, guard on
    ("segments_gqa", 192, 4, 1, 16, dict(block_q=32, block_k=64), True),
    ("segments_gqa_padded", 176, 4, 2, 16, dict(block_q=64, block_k=32), True),
    # blocks taller than _ROWS: a score tile worked off in chunks of rows,
    # query rows in the forward and the query backward, key rows in the
    # key/value backward
    ("row_chunks", 1024, 2, 1, 16, dict(block_q=512, block_k=512), False),
    ("row_chunks_segments", 512, 2, 1, 16, dict(block_q=512, block_k=512),
     True),
]


@pytest.mark.parametrize("s,hq,hkv,d,kwargs,segments",
                         [c[1:] for c in BLOCK_CLASS_CASES],
                         ids=[c[0] for c in BLOCK_CLASS_CASES])
def test_block_classes_match_dense(s, hq, hkv, d, kwargs, segments):
    """Forward and gradients against the dense path with every class of
    block on the grid, in the forward's orientation (queries as rows) and
    the key/value backward's (keys as rows)."""
    q, w = _rand((2, s, hq, d), 0), _rand((2, s, hq, d), 3)
    k, v = _rand((2, s, hkv, d), 1), _rand((2, s, hkv, d), 2)
    causal = kwargs.get("causal", True)
    segs = mask = None
    if segments:
        segs = jnp.asarray(np.sort(
            np.random.RandomState(4).randint(0, 4, (2, s))).astype(np.int32))
        mask = _seg_mask(segs, segs)

    def flash(q, k, v):
        return flash_attention(q, k, v, segment_ids=segs, interpret=True,
                               **{"causal": True, **kwargs})

    def dense(q, k, v):
        return dot_product_attention(q, k, v, causal=causal, mask=mask)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5)
    for a, b, name in zip(_grads(flash, q, k, v, w),
                          _grads(dense, q, k, v, w), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("q_offset,k_offset,blocks", [
    (40, 0, (32, 32)),    # diagonal 40 keys off the block boundary
    (40, 0, (64, 16)),
    (0, 24, (32, 32)),    # the first 24 queries see no key at all: guard on
    (8, 20, (16, 64)),
    (96, 0, (32, 32)),    # a past hop under the causal flag: all interior
], ids=["q40_32x32", "q40_64x16", "k24_32x32", "q8k20_16x64", "q96_all_past"])
def test_ring_hop_offsets_with_dlse_match_dense(q_offset, k_offset, blocks):
    """Static offsets shift the diagonal off the block boundary; the LSE is
    an output with a cotangent of its own, as a ring hop's merge has it."""
    from tpucfn.kernels import flash_attention_with_lse
    from tpucfn.ops.attention import dot_product_attention_with_lse

    s, h, hkv, d = 96, 4, 2, 16
    q, w = _rand((1, s, h, d), 10), _rand((1, s, h, d), 13)
    k, v = _rand((1, s, hkv, d), 11), _rand((1, s, hkv, d), 12)
    u = _rand((1, s, h), 14)

    def loss(fn, **kw):
        def f(q, k, v):
            o, lse = fn(q, k, v, causal=True, q_offset=q_offset,
                        k_offset=k_offset, **kw)
            # a row that sees no key has lse = NEG_INF on both paths
            return jnp.sum(o * w) + jnp.sum(
                jnp.where(lse > -1e29, lse, 0.0) * u), (o, lse)
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    gf, (of, lf) = loss(flash_attention_with_lse, block_q=blocks[0],
                        block_k=blocks[1], interpret=True)(q, k, v)
    gd, (od, ld) = loss(dot_product_attention_with_lse)(q, k, v)
    np.testing.assert_allclose(np.asarray(of), np.asarray(od), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld), rtol=1e-5,
                               atol=2e-5)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("d,hq,hkv", [(128, 4, 2), (256, 4, 1)],
                         ids=["d128_gqa", "d256_gqa"])
def test_bfloat16_is_held_to_the_dense_paths_bfloat16(d, hq, hkv):
    """The dtype rule: bfloat16 operands to every product, float32
    accumulation and statistics, as the dense path computes. The kernel's
    distance from the float32 answer stays within the dense path's own."""
    s = 256
    q32, w = _rand((1, s, hq, d), 20), _rand((1, s, hq, d), 23)
    k32, v32 = _rand((1, s, hkv, d), 21), _rand((1, s, hkv, d), 22)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    # the float32 answer for the rounded inputs
    exact = (q.astype(jnp.float32), k.astype(jnp.float32),
             v.astype(jnp.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                               interpret=True).astype(jnp.float32)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, causal=True).astype(jnp.float32)

    assert flash_attention(q, k, v, interpret=True).dtype == jnp.bfloat16
    ref = [dense(*exact), *_grads(dense, *exact, w)]
    got_flash = [flash(q, k, v), *_grads(flash, q, k, v, w)]
    got_dense = [dense(q, k, v), *_grads(dense, q, k, v, w)]
    for r, f, dn, name in zip(ref, got_flash, got_dense,
                              ("o", "dq", "dk", "dv")):
        assert f.dtype == dn.dtype
        r = np.asarray(r, np.float32)
        err_flash = np.linalg.norm(np.asarray(f, np.float32) - r)
        err_dense = np.linalg.norm(np.asarray(dn, np.float32) - r)
        assert err_flash <= 1.25 * err_dense + 1e-6, (name, err_flash,
                                                     err_dense)


def test_float32_inputs_compute_what_the_parent_computed():
    """float32 inputs take no part in the dtype rule: forward, LSE and
    gradients equal the kernels' results before PR 30 (recorded from that
    tree, interpret mode, same inputs) to float32 tolerance, for the three
    ways a block can be an edge."""
    import pathlib

    want = np.load(pathlib.Path(__file__).parent / "data"
                   / "flash_f32_parent.npz")
    from tpucfn.kernels import flash_attention_with_lse

    rs = np.random.RandomState(30)
    b, s, hq, hkv, d = 1, 72, 2, 1, 16
    q = jnp.asarray(rs.randn(b, s, hq, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    w = jnp.asarray(rs.randn(b, s, hq, d).astype(np.float32))
    u = jnp.asarray(rs.randn(b, s, hq).astype(np.float32))
    segs = jnp.asarray(np.sort(rs.randint(0, 3, (b, s))).astype(np.int32))

    def causal(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=16,
                            interpret=True)
        return jnp.sum(o * w), {"o": o}

    def hop(q, k, v):
        o, lse = flash_attention_with_lse(
            q, k, v, causal=True, q_offset=8, k_offset=20, block_q=16,
            block_k=32, interpret=True)
        return (jnp.sum(o * w) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * u),
                {"o": o, "lse": lse})

    def seg(q, k, v):
        o = flash_attention(q, k, v, causal=True, segment_ids=segs,
                            block_q=16, block_k=32, interpret=True)
        return jnp.sum(o * w), {"o": o}

    for name, fn in (("causal", causal), ("hop", hop), ("seg", seg)):
        grads, outs = jax.grad(fn, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        outs.update(dq=grads[0], dk=grads[1], dv=grads[2])
        for key, got in outs.items():
            np.testing.assert_allclose(
                np.asarray(got), want[f"{name}_{key}"], rtol=2e-6, atol=2e-6,
                err_msg=f"{name}_{key}")


GEOMETRIES = [
    # (causal, block_q, block_k, q_offset, k_offset, kv_len, sk_pad, sq_pad)
    (True, 256, 512, 0, 0, 8192, 8192, 8192),
    (True, 128, 32, 0, 0, 256, 256, 256),
    (True, 32, 128, 0, 0, 200, 256, 224),
    (True, 32, 32, 40, 0, 96, 96, 96),
    (True, 16, 64, 8, 20, 72, 128, 80),
    (True, 32, 32, 0, 1000, 32, 32, 32),
    (False, 64, 64, 0, 0, 200, 256, 256),
    (False, 64, 32, 0, 0, 192, 192, 192),
]


@pytest.mark.parametrize("geometry", GEOMETRIES,
                         ids=["-".join(map(str, g)) for g in GEOMETRIES])
def test_grid_classes_and_clamps_against_the_mask_itself(geometry):
    """A block's class and the index maps' clamps, held to the mask written
    out pair by pair: interior blocks mask nothing, skipped blocks keep
    nothing, and a skipped step's clamped index is a block that is needed
    (so it is resident and the step fetches nothing)."""
    from tpucfn.kernels.flash_attention import _Grid

    causal, bq, bk, qoff, koff, kv_len, sk_pad, sq_pad = geometry
    grid = _Grid(causal, bq, bk, qoff, koff, kv_len, sk_pad, False)
    qpos = qoff + np.arange(sq_pad)[:, None]
    kloc = np.arange(sk_pad)[None, :]
    keep = np.broadcast_to(kloc < kv_len, (sq_pad, sk_pad))
    if causal:
        keep = keep & (qpos >= koff + kloc)
    nq, nk = sq_pad // bq, sk_pad // bk
    counted = {"interior": 0, "edge": 0, "skipped": 0}
    for qi in range(nq):
        for ki in range(nk):
            block = keep[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            needed = bool(grid.needed(qi, ki))
            interior = bool(grid.interior(qi, ki))
            assert not interior or block.all(), (qi, ki)
            assert needed or not block.any(), (qi, ki)
            assert needed or not interior
            # where causality skips, it is the only thing that masks
            if causal and not needed:
                assert (qoff + (qi + 1) * bq - 1) < koff + ki * bk
            counted["interior" if interior else
                    "edge" if needed else "skipped"] += 1
            # a needed step's index maps are the identity; a skipped step's
            # hold the nearest needed block of its row (column), which the
            # step before fetched or the step after will
            k_held = int(grid.last_needed_k(qi, ki))
            q_held = int(grid.first_needed_q(qi, ki, nq))
            if needed:
                assert (k_held, q_held) == (ki, qi)
            if not needed and any(grid.needed(qi, j) for j in range(nk)):
                assert grid.needed(qi, k_held)
                assert not grid.needed(qi, k_held + 1)
            if not needed and any(grid.needed(i, ki) for i in range(nq)):
                assert grid.needed(q_held, ki)
                assert q_held == 0 or not grid.needed(q_held - 1, ki)
    assert grid.count(sq_pad) == counted
    if geometry == GEOMETRIES[0]:
        assert counted == {"interior": 240, "edge": 32, "skipped": 240}


# ---- values narrower (or wider) than keys: latent attention's 192 / 128 ----

# (id, S, query heads, key heads, key size, value size, kwargs, segments?)
VALUE_WIDTH_CASES = [
    # the latent attention's sizes themselves: 192 is no multiple of the lanes
    ("mla_192v128", 64, 2, 2, 192, 128, dict(block_q=32, block_k=32), False),
    ("narrow_48v32_gqa", 96, 4, 2, 48, 32, dict(block_q=32, block_k=32), False),
    ("wider_values_16v40", 64, 2, 1, 16, 40, dict(block_q=16, block_k=32), False),
    ("padded_keys_24v8", 50, 2, 2, 24, 8, dict(block_q=16, block_k=16), False),
    ("segments_32v16", 64, 2, 2, 32, 16, dict(block_q=16, block_k=16), True),
    ("full_40v24", 48, 2, 2, 40, 24, dict(causal=False), False),
]


@pytest.mark.parametrize("s,hq,hkv,d,dv,kwargs,segments",
                         [c[1:] for c in VALUE_WIDTH_CASES],
                         ids=[c[0] for c in VALUE_WIDTH_CASES])
def test_value_width_differs_from_key_width(s, hq, hkv, d, dv, kwargs, segments):
    """Forward and all three gradients against the dense path when ``v`` (and
    so ``o``, ``do``, ``dv``) has another head size than ``q`` and ``k``: the
    scale is the keys', every accumulator has its own array's width."""
    rng = jax.random.key(d * 1000 + dv)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (2, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, s, hkv, dv))
    w = jax.random.normal(jax.random.fold_in(rng, 3), (2, s, hq, dv))
    kwargs = {"causal": True, **kwargs}
    seg = mask = None
    if segments:
        seg = jnp.asarray(np.concatenate(
            [np.zeros((2, 24), np.int32), np.ones((2, s - 24), np.int32)], 1))
        mask = _seg_mask(seg, seg)

    def flash(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, interpret=True, **kwargs)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, causal=kwargs["causal"], mask=mask)

    out, ref = flash(q, k, v), dense(q, k, v)
    assert out.shape == ref.shape == (2, s, hq, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_value_width_with_lse_and_its_cotangent():
    from tpucfn.kernels.flash_attention import flash_attention_with_lse
    from tpucfn.ops.attention import dot_product_attention_with_lse

    rng = jax.random.key(5)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (1, 64, 2, 48))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 64, 2, 48))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 64, 2, 32))

    def loss(fn, **kw):
        def f(q, k, v):
            o, lse = fn(q, k, v, causal=True, **kw)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    (lf, gf) = loss(flash_attention_with_lse, interpret=True, block_q=32,
                    block_k=32)
    (ld, gd) = loss(dot_product_attention_with_lse)
    assert float(lf) == pytest.approx(float(ld), rel=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_the_table_key_learns_the_value_size_and_keeps_its_rows():
    from tpucfn.kernels import flash_autotune as ft

    assert ft._key("TPU v5 lite", True, 8192, 128, jnp.bfloat16) \
        == ft._key("TPU v5 lite", True, 8192, 128, jnp.bfloat16, 128) \
        == "TPU v5 lite|causal|8192|128|bfloat16"
    assert ft._key("TPU v5 lite", True, 8000, 192, jnp.bfloat16, 128) \
        == "TPU v5 lite|causal|8192|192v128|bfloat16"


# ---- PR 33: a scale the model states, and head size 64 ----

# (id, S, q heads, kv heads, D, scale, kwargs of flash_attention)
SCALE_CASES = [
    # granite-4.0-h's attention: 4 query heads a key head, heads of 64, 1/64
    ("hd64_gqa_one_64th", 96, 8, 2, 64, 1 / 64, dict(block_q=32, block_k=32)),
    ("hd64_padded_edge", 100, 4, 4, 64, 1 / 64, dict(block_q=64, block_k=32)),
    ("hd32_twice_the_default", 64, 4, 2, 32, 2 * 32 ** -0.5, {}),
    ("hd64_default_scale", 96, 8, 2, 64, None, dict(block_q=32, block_k=64)),
]


@pytest.mark.parametrize("s,hq,hkv,d,scale,kwargs", [c[1:] for c in SCALE_CASES],
                         ids=[c[0] for c in SCALE_CASES])
def test_a_stated_scale_matches_dense_forward_and_all_three_gradients(
        s, hq, hkv, d, scale, kwargs):
    """``scale`` multiplies the scores in all three kernels; None is the keys'
    ``d ** -0.5``.  Held to the dense path fed queries multiplied by the ratio
    of the two scales (the same scores without the argument), and to the dense
    path's own ``scale``."""
    q, k, v, w = (_rand((2, s, h, d), i) for i, h in enumerate((hq, hkv, hkv, hq)))
    ratio = 1.0 if scale is None else scale / d ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale, interpret=True,
                               **kwargs)

    def dense(q, k, v):
        return dot_product_attention(q * ratio, k, v, causal=True)

    out, ref = flash(q, k, v), dense(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(dot_product_attention(q, k, v, causal=True, scale=scale)),
        np.asarray(ref), atol=2e-5)
    if scale is not None:   # and the default would not have done
        assert float(jnp.max(jnp.abs(
            flash_attention(q, k, v, causal=True, interpret=True, **kwargs)
            - ref))) > 1e-2
    for a, b, name in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_the_default_scale_lowers_as_the_keys_own_and_another_does_not():
    """Every call that names no scale lowers to the text it lowered to when
    the kernels fixed ``d ** -0.5`` themselves (forward and backward)."""
    q, k, v = _qkv(b=1, sq=64, sk=64, hq=4, hkv=2, d=32)

    def text(**kw):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True, **kw)), argnums=(0, 1, 2))
        ).lower(q, k, v).as_text()

    assert text() == text(scale=None) == text(scale=32 ** -0.5)
    assert text() != text(scale=1 / 64)
    from tpucfn.kernels.auto import auto_attention_static_zero
    np.testing.assert_allclose(
        np.asarray(auto_attention_static_zero(q, k, v, scale=1 / 64)),
        np.asarray(dot_product_attention(q * (32 ** 0.5 / 64), k, v)), atol=2e-5)
