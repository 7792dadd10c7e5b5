"""The main path's kernels compile for the real chip — without the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a
*described* v5e (guide ``on-chip-measurement`` §2.3).  Every other flash
test runs ``interpret=True``, which cannot see what Mosaic refuses (a
slice off the tiling, too much fast memory); these cases can, at real
widths, about two seconds each and no chip time.

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture — nothing at import time, not in
conftest.py, not autouse, no child process: one process at a time may
load the TPU library, xdist workers each import every test file, and a
file that decides its tests at import gives workers different collections.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    # conftest.py strips every TPU_* variable; without this the compiler
    # logs under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


# (id, S, D, q heads, kv heads, flash_attention kwargs, segment_ids?)
CASES = [
    ("s2048_d128_b512x256", 2048, 128, 32, 8,
     dict(block_q=512, block_k=256), False),
    ("s4096_d128_b256x512", 4096, 128, 32, 8,
     dict(block_q=256, block_k=512), False),
    ("s8192_d128_b256x512", 8192, 128, 32, 8,
     dict(block_q=256, block_k=512), False),
    # the blocks mistral7b-s8192 and qwen3next-ep8-s8192 read from the table
    # since PR 30: the largest score tile, 4 MB in float32
    ("s8192_d128_b1024x1024", 8192, 128, 32, 8,
     dict(block_q=1024, block_k=1024), False),
    ("s8192_d256_gqa16x2_b1024x1024", 8192, 256, 16, 2,
     dict(block_q=1024, block_k=1024), False),
    ("s2048_d64_default", 2048, 64, 32, 8, {}, False),
    ("s8192_d64_default", 8192, 64, 32, 8, {}, False),
    ("s4096_d40_unet_full", 4096, 40, 8, 8, dict(causal=False), False),
    ("s2048_d128_segment_ids", 2048, 128, 32, 8,
     dict(block_q=512, block_k=256), True),
    ("s2048_d128_ring_hop_offsets", 2048, 128, 32, 8,
     dict(block_q=512, block_k=256, q_offset=2048, k_offset=0), False),
    ("s2000_d128_padded", 2000, 128, 32, 8, {}, False),
    # the gated attention of models/hybrid.py: 16 query heads on 2 key heads
    # of 256, the one head size past the 128 lanes
    ("s8192_d256_gqa16x2_default", 8192, 256, 16, 2, {}, False),
]


@pytest.mark.parametrize("s,d,h,hkv,kwargs,segments",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, s, d, h, hkv, kwargs,
                                        segments):
    from tpucfn.kernels.flash_attention import flash_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((1, s, h, d), jnp.bfloat16)
    kv = sds((1, s, hkv, d), jnp.bfloat16)
    args = [q, kv, kv] + ([sds((1, s), jnp.int32)] if segments else [])

    def loss(q, k, v, seg=None):
        out = flash_attention(q, k, v, segment_ids=seg, interpret=False,
                              **{"causal": True, **kwargs})
        return jnp.sum(out.astype(jnp.float32))

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    text = step.lower(*args).compile().as_text()
    # forward, dq and dk/dv kernels — compiled, not interpreted
    assert text.count("tpu_custom_call") >= 3, text.count("tpu_custom_call")


@pytest.mark.parametrize("blocks", [(1024, 1024), (512, 512)],
                         ids=["b1024x1024", "b512x512"])
def test_flash_latent_head_sizes_compile_for_v5e(one_chip, blocks):
    """Latent attention's call at the benchmark cell's shape: 32 heads with
    keys of 192 (128 + 64 rotary; the first head size that is no multiple of
    the 128 lanes) and values of 128, 8,192 positions, bfloat16; forward and
    both backward kernels, compiled, not interpreted."""
    from tpucfn.kernels.flash_attention import flash_attention

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False,
                              block_q=blocks[0], block_k=blocks[1])
        assert out.shape == (1, 8192, 32, 128)
        return jnp.sum(out.astype(jnp.float32))

    qk = sds((1, 8192, 32, 192))
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    text = step.lower(qk, qk, sds((1, 8192, 32, 128))).compile().as_text()
    assert text.count("tpu_custom_call") >= 3, text.count("tpu_custom_call")


def test_hybrid_layer_ops_compile_for_v5e(one_chip):
    """The two new ops of models/hybrid.py at the benchmark cell's widths,
    forward and backward: the chunked delta rule (32 value heads of 128 on
    16 key heads, 8,192 positions, bfloat16) and the expert layer's grouped
    product, which the compiler turns into a kernel of its own."""
    from tpucfn.ops.gated_delta import gated_delta_rule

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, s = 1, 8192
    qk = sds((b, s, 16, 128), jnp.bfloat16)
    v = sds((b, s, 32, 128), jnp.bfloat16)
    gb = sds((b, s, 32), jnp.float32)
    delta = jax.jit(jax.grad(
        lambda q, k, v, g, beta: jnp.sum(gated_delta_rule(
            q, k, v, g, beta).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))
    assert "while" in delta.lower(qk, qk, v, gb, gb).compile().as_text()

    rows, experts, d, f = 8192, 64, 2048, 512
    grouped = jax.jit(jax.grad(
        lambda x, w, sizes: jnp.sum(jax.lax.ragged_dot(x, w, sizes).astype(
            jnp.float32)), argnums=(0, 1)))
    text = grouped.lower(sds((rows, d), jnp.bfloat16),
                         sds((experts, d, f), jnp.bfloat16),
                         sds((experts,), jnp.int32)).compile().as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text


def _ssd_step_text(one_chip):
    """The chunked state-space op at the benchmark cell's widths (64 heads of
    64 over one group of state 128, 16,384 positions in chunks of 256,
    bfloat16), value and every gradient, compiled for the chip: its text."""
    from tpucfn.ops.ssd import ssd

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = 16384
    op = jax.jit(jax.value_and_grad(
        lambda x, dt, a, b, c, d: jnp.sum(ssd(x, dt, a, b, c, d)[0].astype(
            jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4, 5)))
    return op.lower(sds((1, s, 64, 64)), sds((1, s, 64), jnp.float32),
                    sds((64,), jnp.float32), sds((1, s, 1, 128)),
                    sds((1, s, 1, 128)), sds((64,), jnp.float32)
                    ).compile().as_text()


def test_state_space_kernels_compile_for_v5e(one_chip, monkeypatch):
    """The path the op takes on the chip: ``ssd_own_fwd``, ``ssd_chunk_fwd``
    and their backward kernels compiled, not interpreted (the backend here is
    the CPU, so the test answers "tpu" for it), around XLA's scan over chunks;
    no array of every chunk's and every head's 256 x 256 decays is left."""
    import functools

    from tpucfn.kernels import ssd as kernels
    from tpucfn.ops import ssd as ssd_op

    monkeypatch.setattr(ssd_op, "_backend", lambda: "tpu")
    for name in ("ssd_chunk", "ssd_own"):
        monkeypatch.setattr(kernels, name, functools.partial(
            getattr(kernels, name), interpret=False))
    text = _ssd_step_text(one_chip)
    assert text.count("tpu_custom_call") == 4, text.count("tpu_custom_call")
    assert "while" in text and "[64,64,256,256]" not in text


def test_state_space_layer_ops_compile_for_v5e(one_chip):
    """What models/ssm.py brings at the benchmark cell's widths, forward and
    backward, compiled for the chip: the chunked state-space op's ``jnp`` form
    (what a CPU backend is given: the decays of every chunk and head at once),
    and the flash kernels at 32 / 8 heads of 64 with the model's own
    scale (a head half the 128 lanes wide) at the blocks its row gives."""
    from tpucfn.kernels.flash_attention import flash_attention

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = 16384
    text = _ssd_step_text(one_chip)
    assert "tpu_custom_call" not in text
    assert "while" in text and "bf16[64,64,256,256]" in text

    attn = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, scale=1 / 64, interpret=False, block_q=1024,
            block_k=1024).astype(jnp.float32)), argnums=(0, 1, 2)))
    kv = sds((1, s, 8, 64))
    text = attn.lower(sds((1, s, 32, 64)), kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3, text.count("tpu_custom_call")


def test_gdn_prep_compiles_for_v5e(one_chip):
    """The delta rule's preparation kernels at the benchmark cell's shapes
    (2 x 8,192 positions in chunks of 64, 16 key heads serving 32 value heads
    of 128, bfloat16), forward and backward: compiled, not interpreted."""
    from tpucfn.kernels.gated_delta import gdn_prep

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, hk, rep, n, c, d = 2, 16, 2, 128, 64, 128
    qk = sds((b, n * c, hk * d), jnp.bfloat16)
    v = sds((b, n * c, hk * rep * d), jnp.bfloat16)
    row = sds((b, hk, rep, n, c), jnp.float32)

    def loss(*a):
        return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                   for o in gdn_prep(*a, interpret=False))

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    text = step.lower(qk, qk, v, row, row).compile().as_text()
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")
