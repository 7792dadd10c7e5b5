"""Llama-3-family decoder — the flagship model (BASELINE config 4:
"Llama-3 8B FSDP-style param sharding on v5p-64").

Architecture (public Llama-3 recipe): RMSNorm pre-norm, GQA attention with
RoPE (theta 500k), SwiGLU MLP, untied LM head. TPU-first choices:

* layers run under ``nn.scan`` + ``nn.remat`` — one compiled block body
  regardless of depth (compile time O(1) in layers) and activation
  rematerialization to trade MXU flops for HBM (the standard TPU memory
  recipe). Scanned params carry a leading layer axis; ``sharding_rules``
  accounts for it.
* bf16 activations, fp32 params/optimizer, fp32 logits for the softmax.
* attention inner op is pluggable (dense XLA / Pallas flash / ring SP).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpucfn.mesh import AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR
from tpucfn.models.layers import (
    AttentionFn,
    CausalSelfAttention,
    RMSNorm,
    SwiGLUMLP,
)
from tpucfn.models.moe import MoEConfig, MoEMLP
from tpucfn.ops.attention import dot_product_attention
from tpucfn.parallel.sharding import ShardingRules


def remat_policy(remat: bool | str):
    """(do_remat, jax.checkpoint policy) for a ``LlamaConfig.remat``
    value — shared by the scanned model and the pipeline stage body so
    both paths honor the same policy vocabulary.

    "full" saves only what the flash kernel names (its output and its
    compact LSE, the two residuals its backward reads, and a selection of
    keys it was handed), so the kernel's forward, and the selection's,
    run once a step; a layer with no flash call holds nothing of those
    names and saves nothing."""
    if remat in (True, "full"):
        from tpucfn.kernels.flash_attention import (FLASH_LSE, FLASH_OUT,
                                                    FLASH_SELECT)

        return True, jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT, FLASH_LSE, FLASH_SELECT)
    if remat in (False, "none"):
        return False, None
    if remat == "dots":
        return True, jax.checkpoint_policies.checkpoint_dots
    if remat == "dots_no_batch":
        return True, jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(
        f"remat={remat!r} — expected True/'full', 'dots', "
        "'dots_no_batch', or False/'none'")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    # Rematerialization policy for the block stack (numerics-identical
    # across all choices — only the flops/HBM schedule differs):
    #   True / "full": checkpoint everything but the flash kernel's
    #     output and LSE (max memory savings, ~1/3 extra recompute flops,
    #     the attention kernel's forward not among them) — the
    #     fits-anywhere default.
    #   "dots": jax.checkpoint_policies.checkpoint_dots — keep matmul
    #     (MXU) outputs, recompute only cheap elementwise ops; the
    #     standard TPU middle ground when activations almost fit.
    #   "dots_no_batch": dots_with_no_batch_dims_saveable — save only
    #     weight-stationary matmuls (Megatron-style selective remat).
    #   False / "none": no remat (pure MFU when the model fits).
    remat: bool | str = True
    moe: MoEConfig | None = None  # None = dense SwiGLU MLP

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def __post_init__(self):
        remat_policy(self.remat)  # validate early, not at first apply

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()  # the defaults above are the 8B shape

    @classmethod
    def llama3_1b(cls) -> "LlamaConfig":
        # ~1B proxy for single-chip benchmarking.
        return cls(dim=2048, n_layers=16, n_heads=32, n_kv_heads=8, ffn_dim=8192)

    @classmethod
    def tiny(cls, vocab: int = 256) -> "LlamaConfig":
        return cls(vocab_size=vocab, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                   ffn_dim=128, max_seq=512, dtype=jnp.float32)


class LlamaBlock(nn.Module):
    """One decoder block. ``__call__`` uses scan's (carry, _) -> (carry, None)
    shape so the same body works unrolled and under ``nn.scan``; q_offset
    rides in the carry because it can be a traced value (ring/SP shards
    derive it from ``lax.axis_index``)."""

    cfg: LlamaConfig
    attention_fn: AttentionFn = dot_product_attention
    decode: bool = False
    # Mesh for the MoE explicit expert-parallel dispatch (models/moe.py);
    # None keeps MoE single-device. Static module metadata, like
    # attention_fn.
    ep_mesh: Any = None
    # True when this block runs inside a shard_map whose manual axes
    # include `expert` (the pipeline stage body): MoE runs its EP body
    # inline with locally-declared expert params (models/moe.py).
    ep_manual: bool = False

    @nn.compact
    def __call__(self, carry, _=None):
        x, q_offset = carry
        cfg = self.cfg
        h = CausalSelfAttention(
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, max_seq=cfg.max_seq, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, attention_fn=self.attention_fn,
            decode=self.decode, name="attn",
        )(RMSNorm(cfg.norm_eps, cfg.dtype, name="input_norm")(x), q_offset=q_offset)
        x = x + h
        normed = RMSNorm(cfg.norm_eps, cfg.dtype, name="post_attn_norm")(x)
        if cfg.moe is not None:
            h = MoEMLP(cfg.ffn_dim, cfg.moe, cfg.dtype, cfg.param_dtype,
                       ep_mesh=self.ep_mesh, ep_manual=self.ep_manual,
                       name="mlp")(normed)
        else:
            h = SwiGLUMLP(cfg.ffn_dim, cfg.dtype, cfg.param_dtype, name="mlp")(normed)
        return (x + h, q_offset), None


class Llama(nn.Module):
    cfg: LlamaConfig
    # None = automatic dense↔flash dispatch (tpucfn.kernels.auto): the
    # Pallas flash kernel on TPU at S >= TPUCFN_FLASH_MIN_S, XLA dense
    # everywhere else. Pass an explicit fn (dense, ring, flash) to pin.
    attention_fn: AttentionFn | None = None
    decode: bool = False  # KV-cache autoregressive mode (generation)
    # Mesh enabling the MoE explicit expert-parallel all-to-all dispatch
    # when its `expert` axis is >1 (tpucfn/models/moe.py). Pass the
    # training mesh; None (default) keeps MoE on the single-device path.
    ep_mesh: Any = None

    @nn.compact
    def __call__(self, tokens, *, q_offset=0, return_hidden=False,
                 segment_ids=None):
        """tokens: (B, S) int32 → logits (B, S, vocab) fp32.

        ``q_offset`` is the global position of tokens[:, 0] — nonzero when
        the sequence axis is sharded (ring attention / SP).

        ``return_hidden=True`` stops after the final norm and returns the
        (B, S, dim) hidden states instead of logits — pair it with
        :func:`chunked_causal_lm_loss`, which applies the LM head
        chunk-by-chunk so the fp32 (B, S, vocab) logits tensor is never
        materialized (at B=8, S=2k, V=128k that tensor alone is ~8 GB —
        more than half a v5e's HBM; observed OOM on chip).  Init with the
        default ``False`` so the head params are created.

        ``segment_ids`` (B, S) enables packed-sequence training:
        attention is masked across document boundaries (the flash
        kernel's native segment path on TPU, an explicit mask on dense)
        — pair with ``packed_causal_lm_loss``.  Overrides
        ``attention_fn``; incompatible with decode/SP.
        """
        if self.decode and not (isinstance(q_offset, int) and q_offset == 0):
            raise ValueError("decode mode is incompatible with q_offset/SP sharding")
        if segment_ids is not None:
            if self.decode:
                raise ValueError("segment_ids is incompatible with decode mode")
            if not (isinstance(q_offset, int) and q_offset == 0):
                raise ValueError(
                    "segment_ids is incompatible with q_offset/SP sharding")
            from tpucfn.data.packing import packed_attention_fn

            attention_fn = packed_attention_fn(segment_ids)
        else:
            attention_fn = self.attention_fn
        if attention_fn is None:
            from tpucfn.kernels.auto import auto_attention_static_zero

            # Flash-eligible only when offsets are the static zero of the
            # unsharded path (decode and SP keep the dense/ring ops).
            if not self.decode and isinstance(q_offset, int) and q_offset == 0:
                attention_fn = auto_attention_static_zero
            else:
                attention_fn = dot_product_attention
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="embed_tokens",
        )(tokens)

        block = LlamaBlock
        do_remat, policy = remat_policy(cfg.remat)
        if do_remat and not self.decode:
            block = nn.remat(block, prevent_cse=False, policy=policy)
        carry = (x, jnp.asarray(q_offset))
        if cfg.scan_layers:
            carry, _ = nn.scan(
                block,
                variable_axes={"params": 0, "losses": 0, "metrics": 0, "cache": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, attention_fn, self.decode, self.ep_mesh,
              name="layers")(carry)
        else:
            for i in range(cfg.n_layers):
                carry, _ = block(cfg, attention_fn, self.decode, self.ep_mesh,
                                 name=f"layers_{i}")(carry)
        x = carry[0]

        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if return_hidden:
            return x
        logits = nn.DenseGeneral(
            cfg.vocab_size, use_bias=False, dtype=jnp.float32,
            param_dtype=cfg.param_dtype, name="lm_head",
        )(x.astype(jnp.float32))
        return logits


def sharding_rules(cfg: LlamaConfig, *, fsdp: bool = True, tensor: bool = True,
                   layer_lead_axis: str | None = None) -> ShardingRules:
    """Megatron TP × FSDP rules for the Llama param tree.

    Scanned layers stack params with a leading ``layers`` axis; every
    spec under ``layers/`` starts with ``layer_lead_axis`` there —
    None (unsharded depth) normally, the pipeline axis for PP stage
    sharding (llama_pp.pp_sharding_rules).  The ``spec()`` helper below
    is used by exactly the per-layer rules, so this composes without
    any pattern-matching on rule strings.
    """
    t = AXIS_TENSOR if tensor else None
    f = AXIS_FSDP if fsdp else None
    lead = (layer_lead_axis,) if cfg.scan_layers else ()

    def spec(*axes):
        full = lead + axes
        while full and full[-1] is None:  # canonical: no trailing Nones
            full = full[:-1]
        return P(*full)

    e = AXIS_EXPERT
    return ShardingRules((
        # MoE experts first (more specific than the dense MLP rules).
        (r"experts/(gate_proj|up_proj)/kernel$", spec(e, f, t)),
        (r"experts/down_proj/kernel$", spec(e, t, f)),
        (r"router/kernel$", spec(f)),
        (r"(q_proj|k_proj|v_proj)/kernel$", spec(f, t)),
        (r"o_proj/kernel$", spec(t, f)),
        (r"(gate_proj|up_proj)/kernel$", spec(f, t)),
        (r"down_proj/kernel$", spec(t, f)),
        (r"(input_norm|post_attn_norm)/scale$", spec()),
        (r"embed_tokens/embedding$", P(t, f)),
        (r"lm_head/kernel$", P(f, t)),
        (r".*", P()),
    ))


# no Flax module, so no scope of its own: this names the head's product for the
# trace's map (obs.program); metadata, nothing that runs
@jax.named_scope("lm_head")
def chunked_causal_lm_loss(
    hidden: jax.Array,          # (B, S, D) — Llama(...)(…, return_hidden=True)
    lm_head_kernel: jax.Array,  # (D, V)
    tokens: jax.Array,          # (B, S) int32
    *,
    chunk_size: int = 512,
    z_loss: float = 0.0,
    ahead: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """Next-token CE + accuracy WITHOUT materializing (B, S, V) logits.

    ``ahead`` is how many positions on a position's target lies: 1 is the
    next token; a multi-token-prediction head passes 2 for the token after
    next, and the last ``ahead`` positions, which have no target, are left
    out of the mean.

    Numerically equal to ``causal_lm_loss(hidden @ W, tokens)`` (tests
    assert values and grads).  The forward pass is a ``lax.scan`` over
    chunks of ``chunk_size`` positions: each chunk's fp32 logits are
    reduced to a CE sum, a correct-count and each row's log-sum-exp, and
    dropped.  Peak logits memory is (B, chunk, V) instead of (B, S, V) —
    the difference between fitting and the observed on-chip OOM for
    Llama-1B (V=128k) on one 16 GB chip, and a hard requirement at the
    long-context end (S=32k never fits materialized).

    The backward pass is a ``jax.custom_vjp`` of its own; its residuals are
    the chunked hidden states, the table, the targets and the rows'
    log-sum-exp, (chunks, B, chunk) fp32.  It recomputes the logits once,
    with the forward's operand dtypes and precision, and walks them in the
    order ``vocab_block`` picks from the shapes: by blocks of the
    vocabulary over all rows (scope ``lm_head/vocab_blocks``: each block's
    columns of the table gradient written once, the fp32 ``dh`` summed over
    blocks), or by the forward's chunks (``lm_head/token_chunks``: each
    chunk's ``dh`` written once, the fp32 table gradient summed over
    chunks).  A block's fp32 logits over all rows stay within
    ``HEAD_BLOCK_BYTES``.  Forward-mode differentiation (``jax.jvp``)
    through the head is not defined.
    """
    b, s, d = hidden.shape
    n = s - ahead
    pred = hidden[:, :-ahead]
    targets = tokens[:, ahead:]
    c = max(1, min(chunk_size, n))
    pad = (-n) % c
    if pad:
        pred = jnp.pad(pred, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    k = (n + pad) // c
    pred = pred.reshape(b, k, c, -1).swapaxes(0, 1)     # (k, B, c, D)
    targets = targets.reshape(b, k, c).swapaxes(0, 1)   # (k, B, c)
    width = vocab_block(b * k * c, k, d, lm_head_kernel.shape[1],
                        h_bytes=hidden.dtype.itemsize,
                        w_bytes=lm_head_kernel.dtype.itemsize)
    ce, cor = _head_sums(pred, lm_head_kernel, targets, float(z_loss), width)
    denom = b * n
    return ce / denom, cor / denom


# The most fp32 logits the head's backward holds at once, in bytes: a
# vocabulary block over every row (PERF.md, section 3, says why this size).
HEAD_BLOCK_BYTES = 256 * 2**20
_LANES = 128


def vocab_block(rows: int, chunks: int, d: int, v: int, *,
                h_bytes: int = 2, w_bytes: int = 4) -> int:
    """The width of the blocks in which the head's backward walks the
    vocabulary, or 0 where it walks the forward's chunks of positions.

    ``rows`` positions, padding included, in ``chunks`` chunks, against a
    ``(d, v)`` table.  By chunks, each chunk reads and writes the fp32 table
    gradient whole and reads the table: ``chunks × (2·|dW| + |W|)`` bytes.
    By blocks, each block reads and writes the fp32 ``dh`` and reads the
    hidden states: ``blocks × (2·|dh| + |h|)``.  The walk that moves fewer
    bytes is taken, a tie by chunks.  Blocks are whole 128-lane tiles, as
    many as keep a block's fp32 logits over all rows within
    ``HEAD_BLOCK_BYTES``, and as even as whole tiles allow (the last one
    narrower where ``v`` is no multiple of the width); a table no wider than
    one block is one block."""
    most = max(_LANES, HEAD_BLOCK_BYTES // (4 * rows) // _LANES * _LANES)
    if v <= most:
        width = v
    else:
        width = -(-v // -(-v // most))          # ceil(v / blocks)
        width = -(-width // _LANES) * _LANES
    blocks = -(-v // width)
    by_chunks = chunks * (2 * 4 + w_bytes) * d * v
    by_blocks = blocks * (2 * 4 + h_bytes) * rows * d
    return width if by_blocks < by_chunks else 0


def _chunk_stats(h, w, t, z_loss):
    """One chunk's fp32 logits reduced: CE sum, correct-count, and each
    row's log-sum-exp."""
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    top = jnp.max(logits, -1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), -1))
    per_tok = lse - jnp.take_along_axis(
        logits, jnp.maximum(t, 0)[..., None], -1)[..., 0]
    if z_loss:
        per_tok = per_tok + z_loss * lse ** 2
    valid = t >= 0
    ce = jnp.sum(jnp.where(valid, per_tok, 0.0))
    correct = jnp.sum(jnp.where(valid, jnp.argmax(logits, -1) == t,
                                False).astype(jnp.float32))
    return ce, correct, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head_sums(pred, w, targets, z_loss, width):
    """(CE sum, correct-count) over ``pred`` (chunks, B, chunk, D) against
    ``w``; ``width`` is ``vocab_block``'s."""
    return _head_sums_fwd(pred, w, targets, z_loss, width)[0]


def _head_sums_fwd(pred, w, targets, z_loss, width):
    def body(carry, xs):
        ce, cor, lse = _chunk_stats(xs[0], w, xs[1], z_loss)
        return (carry[0] + ce, carry[1] + cor), lse

    zero = jnp.zeros((), jnp.float32)
    sums, lse = jax.lax.scan(body, (zero, zero), (pred, targets))
    return sums, (pred, w, targets, lse)


def _dlogits(h, w, t, lse, g, z_loss, col0):
    """The CE sum's cotangent ``g`` on the logits of rows ``h`` against
    columns ``col0 ...`` of the table, ``w`` those columns: ``(softmax ·
    (1 + 2 z lse) − onehot) · g``, zero on rows whose target is -1."""
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    p = jnp.exp(logits - lse[..., None])
    if z_loss:
        p = p * (1 + 2 * z_loss * lse)[..., None]
    hit = t[..., None] == col0 + jnp.arange(w.shape[1])
    return jnp.where(t >= 0, g, 0.0)[..., None] * jnp.where(hit, p - 1, p)


def _rows_dot(h, dlog):
    """hᵀ · dlog over every axis but the last: a block of the table
    gradient."""
    lead = tuple(range(h.ndim - 1))
    return jnp.tensordot(h.astype(jnp.float32), dlog, (lead, lead))


def _by_blocks(pred, w, targets, lse, g, z_loss, width):
    full, tail = divmod(w.shape[1], width)

    def block(carry, start, size):
        dh, dw = carry
        w_v = jax.lax.dynamic_slice_in_dim(w, start, size, axis=1)
        dlog = _dlogits(pred, w_v, targets, lse, g, z_loss, start)
        dh = dh + dlog @ w_v.astype(jnp.float32).T
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, _rows_dot(pred, dlog), start, axis=1)
        return dh, dw

    carry = (jnp.zeros(pred.shape, jnp.float32),
             jnp.zeros(w.shape, jnp.float32))
    # the start made from the loop's counter in the body, where the compiler
    # sees it a multiple of the width: a write whose offset it cannot show
    # aligned to the lane tiles takes the slow path
    carry = jax.lax.fori_loop(
        0, full, lambda i, cr: block(cr, i * width, width), carry)
    if tail:
        carry = block(carry, full * width, tail)
    return carry


def _by_chunks(pred, w, targets, lse, g, z_loss):
    w32 = w.astype(jnp.float32)

    def body(dw, xs):
        h_c, t_c, lse_c = xs
        dlog = _dlogits(h_c, w, t_c, lse_c, g, z_loss, 0)
        return dw + _rows_dot(h_c, dlog), (dlog @ w32.T).astype(pred.dtype)

    dw, dh = jax.lax.scan(body, jnp.zeros(w.shape, jnp.float32),
                          (pred, targets, lse))
    return dh, dw


def _head_sums_bwd(z_loss, width, res, cts):
    pred, w, targets, lse = res
    g = cts[0]   # the correct-count's cotangent is zero: argmax is flat
    if width:
        with jax.named_scope("vocab_blocks"):
            dh, dw = _by_blocks(pred, w, targets, lse, g, z_loss, width)
    else:
        with jax.named_scope("token_chunks"):
            dh, dw = _by_chunks(pred, w, targets, lse, g, z_loss)
    return dh.astype(pred.dtype), dw.astype(w.dtype), None


_head_sums.defvjp(_head_sums_fwd, _head_sums_bwd)


def causal_lm_loss(logits: jax.Array, tokens: jax.Array,
                   *, z_loss: float = 0.0) -> tuple[jax.Array, jax.Array]:
    """Next-token cross entropy (mean over B, S-1) + optional z-loss.

    Returns (loss, accuracy)."""
    import optax

    targets = tokens[:, 1:]
    pred = logits[:, :-1]
    ce = optax.softmax_cross_entropy_with_integer_labels(pred, targets).mean()
    if z_loss:
        ce = ce + z_loss * jnp.mean(jax.nn.logsumexp(pred, axis=-1) ** 2)
    acc = jnp.mean(jnp.argmax(pred, -1) == targets)
    return ce, acc
