"""Automatic dense↔flash attention dispatch (VERDICT r2 item 3/weak 5).

The Pallas flash kernel is the right default above a sequence-length
threshold on TPU; XLA dense attention is the right default everywhere
else (short S, CPU tests, masked/bidirectional shapes the kernel does
not support). This module owns that policy so models and ring hops
share one rule, and since round 5 the rule is MEASUREMENT-BACKED per
shape family (VERDICT r4 #5 — the round-4 UNet regression showed a
size threshold alone dispatches flash where it loses):

* ``should_use_flash(s, d=..., dtype=..., dv=...)`` — False off-TPU or
  below ``flash_threshold()``; above it, consult the tune table's measured
  dense/flash ratio for the (S, D, dtype) family, ``D`` read ``<D>v<DV>``
  where values and keys differ in head size
  (``flash_autotune.lookup_speedup``): tuned-and-winning → flash,
  tuned-and-losing → dense, never-measured → flash only at
  ``untuned_flash_min_s()`` and beyond (where dense is 15x slower or
  OOMs outright, measured r3).
* ``flash_threshold()`` — ``TPUCFN_FLASH_MIN_S`` (default 2048;
  measured r3 on v5e with the shipped table: fwd+bwd vs dense 1.16x at
  S=2k, 1.88x at 4k, 15.1x at 8k, flash-only at 32k — those ratios now
  live IN the table and drive the per-family rule above).
* ``untuned_flash_min_s()`` — ``TPUCFN_FLASH_UNTUNED_MIN_S`` (default
  8192): the no-evidence fallback boundary.

Dispatch sites:
* :class:`tpucfn.models.llama.Llama` with ``attention_fn=None`` (the
  default) resolves here per call — flash only when the call's
  ``q_offset`` is the static 0 of the non-sequence-parallel path (the
  kernel takes static offsets; SP shards use ring attention instead).
* :func:`tpucfn.kernels.ring_attention.ring_attention` with
  ``hop_attention="auto"`` (the default) routes each hop through the
  flash kernel by the same rule on the LOCAL shard length.
"""

from __future__ import annotations

import os


def flash_threshold() -> int:
    return int(os.environ.get("TPUCFN_FLASH_MIN_S", "2048"))


def untuned_flash_min_s() -> int:
    """Above this length flash is the default even for a shape family
    with NO measured dense comparison: the dense path's O(S^2) score
    tensor is catastrophic there (measured: 15x at S=8k with tuning,
    dense OOMs outright at 32k). Below it, an unmeasured family runs
    dense — the round-4 UNet regression (untuned D=40 flash 10.47
    latents/s vs dense 14.09) is exactly the case this guards."""
    return int(os.environ.get("TPUCFN_FLASH_UNTUNED_MIN_S", "8192"))


def _backend() -> str:
    # A backend that fails to initialise raises: reading it as "cpu"
    # would silently run dense on a machine whose chip did not start.
    import jax

    return jax.default_backend()


_warned_untuned_kinds: set[str] = set()


def _warn_once_if_kind_untuned() -> None:
    """One-time (per device kind, per process) warning when the CURRENT
    device kind has ZERO flash-tune table entries: every shape family
    then runs dense between ``flash_threshold`` and
    ``untuned_flash_min_s`` — a correct but silent fallback that cost a
    round-4 regression hunt to discover (ADVICE r5).  The warning names
    the fix (run ``flash_autotune.tune``) instead of leaving the
    operator to diff HLO dumps."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind in _warned_untuned_kinds:
        return
    _warned_untuned_kinds.add(kind)  # scan the table once per kind
    from tpucfn.kernels.flash_autotune import kind_has_entries

    if not kind_has_entries(kind):
        import warnings

        warnings.warn(
            f"TPU device kind {kind!r} has no flash-tune table entries: "
            f"sequence lengths in [{flash_threshold()}, "
            f"{untuned_flash_min_s()}) will silently use DENSE attention. "
            "Run tpucfn.kernels.flash_autotune.tune(s, d) on this device "
            "(or lower TPUCFN_FLASH_UNTUNED_MIN_S) to enable flash where "
            "it wins.", stacklevel=3)


def _evidence_says_flash(s: int, d, dtype, causal: bool, dv=None) -> bool:
    """Measurement-backed dispatch core (VERDICT r4 #5): consult the
    tune table's measured dense/flash ratio for this (S, D, dtype)
    family (``dv``: the values' head size where it is not ``d``). Tuned and winning (>=5%) → flash; tuned and losing → dense;
    never measured → flash only past ``untuned_flash_min_s``."""
    if d is None:
        # Legacy call sites without a head-dim: length threshold only
        # (preserves their observed behavior; all in-repo sites pass d).
        return True
    from tpucfn.kernels.flash_autotune import lookup_speedup

    speedup = lookup_speedup(int(s), int(d), dtype, causal, dv)
    if speedup is not None:
        return speedup >= 1.05
    if int(s) < untuned_flash_min_s():
        _warn_once_if_kind_untuned()
        return False
    return True


def should_use_flash(s: int, *, causal: bool = True, mask=None,
                     d: int | None = None, dtype=None,
                     dv: int | None = None) -> bool:
    """One policy for every dispatch site. ``s`` must be a static int
    (trace-time shape). Pass ``d``/``dtype`` (the head dim and element
    type) so the decision can consult MEASURED per-family evidence —
    without them only the length threshold applies."""
    if mask is not None or not causal:
        return False  # kernel supports causal/segment masking only
    if _backend() != "tpu" or int(s) < flash_threshold():
        return False
    return _evidence_says_flash(s, d, dtype, causal=True, dv=dv)


def should_use_flash_full(s_q: int, s_kv: int, *, mask=None,
                          d: int | None = None, dtype=None,
                          dv: int | None = None) -> bool:
    """Non-causal (full) attention policy: the dense path materializes a
    (B, H, s_q, s_kv) score tensor, so flash pays when BOTH sides are
    long (a 77-key cross-attention's scores are tiny — dense wins).
    Observed on chip: SD-UNet's 64x64 spatial self-attention (s=4096)
    OOMs dense at batch 8 via 4G fp32 score temps — but routing it
    through UNTUNED flash at batch 4 measured SLOWER than dense
    (round 4), so the same evidence rule applies here."""
    if mask is not None:
        return False
    t = flash_threshold()
    if _backend() != "tpu" or int(s_q) < t or int(s_kv) < t:
        return False
    return _evidence_says_flash(s_q, d, dtype, causal=False, dv=dv)


def full_attention_auto(q, k, v, *, mask=None):
    """Dense↔flash dispatch for non-causal attention call sites (UNet
    spatial/cross attention). Layout (B, S, H, D) like every AttentionFn."""
    if should_use_flash_full(q.shape[1], k.shape[1], mask=mask,
                             d=q.shape[-1], dtype=q.dtype, dv=v.shape[-1]):
        from tpucfn.kernels.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=False)
    from tpucfn.ops.attention import dot_product_attention

    return dot_product_attention(q, k, v, causal=False, mask=mask)


def auto_attention_static_zero(q, k, v, *, causal=True, mask=None,
                               q_offset=0, k_offset=0, scale=None):
    """AttentionFn for call sites whose offsets are STATICALLY zero but
    arrive as traced zeros (Llama's scan carry, the PP stage body):
    dispatches on the local (trace-time) sequence length and DROPS the
    traced zero offsets when taking the flash path — the kernel takes
    static offsets. The caller is responsible for only installing this
    where q_offset/k_offset are provably zero.  ``scale`` multiplies the
    scores on either path; None is the keys' ``D ** -0.5``."""
    if mask is None and should_use_flash(q.shape[1], causal=causal,
                                         d=q.shape[-1], dtype=q.dtype,
                                         dv=v.shape[-1]):
        from tpucfn.kernels.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale)
    from tpucfn.ops.attention import dot_product_attention

    return dot_product_attention(q, k, v, causal=causal, mask=mask,
                                 q_offset=q_offset, k_offset=k_offset,
                                 scale=scale)


def auto_attention(q, k, v, *, causal=True, mask=None, q_offset=0,
                   k_offset=0, segment_ids=None):
    """AttentionFn-shaped dispatcher for call sites whose offsets are
    static Python ints (bench harnesses, direct use). Model integration
    goes through Llama's attention_fn=None resolution instead, because
    scan carries make in-model offsets traced."""
    from tpucfn.kernels.flash_attention import flash_attention
    from tpucfn.ops.attention import dot_product_attention

    static_offsets = isinstance(q_offset, int) and isinstance(k_offset, int)
    if static_offsets and should_use_flash(q.shape[1], causal=causal,
                                           mask=mask, d=q.shape[-1],
                                           dtype=q.dtype, dv=v.shape[-1]):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               k_offset=k_offset, segment_ids=segment_ids)
    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids on the dense fallback path is not wired; pass an "
            "explicit mask or use flash_attention directly")
    return dot_product_attention(q, k, v, causal=causal, mask=mask,
                                 q_offset=q_offset, k_offset=k_offset)


def serve_decode_attention_fn(cache_len: int):
    """Attention path for the serving engine's decode-mode model
    (tpucfn/serve/engine.py) — the one dispatch site where offsets are
    TRACED per slot (each slot's cache index rides the vmapped cache),
    so the Pallas flash kernel (static offsets, blocked s_q) is off the
    table regardless of length.  Single-token decode over a contiguous
    cache is memory-bound gather work XLA handles well; the win past
    this is a dedicated paged/flash-decode kernel keyed on block tables,
    which slots in HERE when it lands (ROADMAP serving follow-ons) —
    models and the engine keep calling this one policy point.

    ``cache_len`` is accepted (and deliberately unused today) so the
    future kernel can pick block shapes without an engine-side change.
    """
    from tpucfn.ops.attention import dot_product_attention as dense

    del cache_len  # reserved for the paged-decode kernel's block picker
    return dense
