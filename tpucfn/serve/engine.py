"""ServeEngine — jitted prefill/decode steps over a slot-resident KV cache.

The engine owns ``max_batch`` physical decode slots.  Each slot carries
its own flax decode cache (the same ``cache`` collection
``models/generate.py`` uses), batched on a leading slot axis, so decode
is ONE jitted program over all slots via ``jax.vmap`` of the
single-sequence apply — per-slot ``cache_index`` scalars fall out of the
vmap for free, which is exactly what continuous batching needs (every
slot sits at a different sequence position) and what the training-style
shared-scalar cache cannot express.

Three compiled entry points, each with the big slot cache DONATED (the
multi-hundred-MB buffer is updated in place, never double-buffered):

* ``prefill_batch``: up to ``prefill_width`` sequences, each padded to
  the SAME length bucket, run through the decode-mode model as one
  vmapped pass.  Each lane carries its own cache START offset: a lane
  with ``start > 0`` continues from a prefix that ``copy_prefix``
  already planted in its slot (positions ``[0, start)``), so a prefix
  cache hit prefills only the suffix.  After the pass each lane's
  per-layer ``cache_index`` is set to its TRUE total length, so bucket
  pad garbage beyond it is overwritten by the next decode step before
  causality could ever expose it; the fresh rows are scattered into the
  donated slot cache and each first token is sampled from the last REAL
  position's logits.  Partial batches pad by repeating lane 0 (the
  duplicate writes the same row twice — idempotent), so the program
  compiles once per (bucket), never per batch size.
* ``decode``: one token for EVERY slot (fixed shape, compiles once).
  Vacant slots compute garbage lanes that are never read — the standard
  static-shape trade.
* ``copy_prefix``: whole-row KV copy from a backer slot plus a
  ``cache_index`` set to the shared prefix length (compiles once; the
  length is a traced scalar).  Bytes past the prefix are stale backer
  state, dead by the same write-before-read causality argument as the
  bucket padding.

Two more entry points exist for speculative decoding (ISSUE 14) and are
built LAZILY on first use, so an engine that never speculates carries
exactly the three programs above and nothing else:

* ``verify``: score ``width`` token positions for EVERY slot in one
  dispatch — the propose-verify round's target-model half.  Each slot's
  input row is its last emitted token followed by ``width - 1`` draft
  proposals; position 0 is sampled exactly as ``decode`` samples (same
  ``_sample``, same temps array, same key fold), positions 1+ are
  greedy argmax (draft acceptance is defined for greedy decode only).
  The pass is a peek: K/V rows gain the ``width`` new entries but every
  ``cache_index`` is restored inside the program — ``rollback`` then
  advances accepted slots to what actually landed.
* ``rollback``: set selected slots' ``cache_index`` to given lengths
  (masked — unselected slots, including free slots holding prefix-cache
  residue, are untouched).  K/V written past the accepted position
  stays in the buffer but is dead: the next step writes position
  ``len`` before anything attends past it — the same causality argument
  the bucket-pad rewind rests on.

Sampling temperatures live in a DEVICE-resident ``(max_batch,)`` array
updated inside the prefill program, so the steady-state decode loop
transfers one token per active slot and nothing else (ISSUE 3
satellite: no more per-step host->device temps upload).

Greedy decode here is token-identical to ``models/generate.py`` (the
parity test in ``tests/test_serve_engine.py`` pins it): same model code,
same cache math, same argmax.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpucfn.parallel.sharding import _path_str


def _maybe_warm(jitted, label: str):
    """Fleet warm start (ISSUE 13): route through the compile-artifact
    cache when a process-default client is configured; otherwise
    ``maybe_warm`` returns the jitted callable itself, untouched."""
    from tpucfn.compilecache.jit import maybe_warm

    return maybe_warm(jitted, label=label)


def _sample(logits: jax.Array, temps: jax.Array, key: jax.Array) -> jax.Array:
    """(N, V) fp32 logits -> (N,) int32 tokens.  temp<=0 is greedy;
    otherwise categorical over logits/temp (the ``models/generate.py``
    convention — temperature scaling first)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _set_cache_index(cache, length):
    """Set every ``cache_index`` leaf (shape (L,) under nn.scan, ()
    unrolled) to ``length``.  Used both to START a pass at a prefix
    offset and to REWIND after a bucketed pass, un-counting the pad:
    K/V beyond ``length`` stays in the buffer but is dead — the next
    step overwrites position ``length`` before attending, and causality
    masks everything past the query."""

    def fix(path, leaf):
        if _path_str(path).endswith("cache_index"):
            return jnp.full(leaf.shape, length, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


class ServeEngine:
    """Wraps any decode-protocol flax model (init/apply with a ``cache``
    collection, ``(B, S) int32 -> (B, S, V)`` logits) behind the jitted
    serving steps.  Use :meth:`from_llama` for the model zoo's decoder
    (optionally LoRA-merged via ``train/lora.py``)."""

    def __init__(self, model: Any, params: Any, *, max_batch: int,
                 cache_len: int, rng: jax.Array | None = None,
                 prefill_width: int = 4):
        from tpucfn.models.hybrid import refuse_recurrent_model

        refuse_recurrent_model(model, "serve")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        # Fixed lane count of the batched prefill program.  Width-K
        # prefill wastes (K - n)/K of the pass on partial batches (lanes
        # duplicate lane 0), the same trade as vacant decode lanes —
        # size it to the workload's admission burstiness.
        self.prefill_width = max(1, int(prefill_width))
        self._base_key = jax.random.key(0) if rng is None else rng
        self._step_count = 0

        # Single-sequence cache template (b=1) — the per-slot unit.
        row_shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((1, 1), jnp.int32)))["cache"]
        self._row_shapes = row_shapes
        # Slot-batched cache: every leaf gains a leading (max_batch,) axis.
        self.cache = jax.tree.map(
            lambda s: jnp.zeros((max_batch,) + s.shape, s.dtype), row_shapes)
        # Device-resident per-slot sampling temperature, written only by
        # the prefill program (decode reads it in place).
        self._temps = jnp.zeros((max_batch,), jnp.float32)

        # Fleet warm start (ISSUE 13): when a compile-artifact client is
        # configured (cmd_serve does it from TPUCFN_COMPILE_CACHE_ADDRS
        # before building engines), each program's first call per shape
        # bucket fetches the serialized executable a peer replica (or a
        # previous incarnation — relaunch, probation) already compiled
        # instead of recompiling.  No client ⇒ the plain jit callables,
        # byte-identical (pinned).
        self._prefill_jit = _maybe_warm(
            jax.jit(self._prefill_many_impl, donate_argnums=(0, 1)),
            "serve_prefill")
        self._decode_jit = _maybe_warm(
            jax.jit(self._decode_impl, donate_argnums=(0,)),
            "serve_decode")
        self._copy_prefix_jit = _maybe_warm(
            jax.jit(self._copy_prefix_impl, donate_argnums=(0,)),
            "serve_copy_prefix")
        # Speculative-decoding programs (ISSUE 14), built on first use so
        # a plain engine's program set (and compile_counts surface) is
        # byte-identical to the pre-spec engine's.
        self._verify_jit = None
        self._rollback_jit = None

    @classmethod
    def from_llama(cls, cfg, params, *, max_batch: int = 8,
                   cache_len: int | None = None, lora_adapters=None,
                   lora_scale: float = 1.0, rng: jax.Array | None = None,
                   prefill_width: int = 4):
        """Engine over the flagship decoder.  ``cache_len`` sizes every
        slot's KV buffer (default ``cfg.max_seq``); ``lora_adapters``
        (from ``train.lora.lora_init``-shaped trees) are merged into the
        weights once, host-side — serving then runs the plain decoder,
        no per-step merge cost."""
        from tpucfn.kernels.auto import serve_decode_attention_fn
        from tpucfn.models.hybrid import refuse_recurrent_model
        from tpucfn.models.llama import Llama

        refuse_recurrent_model(cfg, "serve")
        cache_len = cache_len or cfg.max_seq
        dcfg = dataclasses.replace(cfg, max_seq=cache_len)
        if lora_adapters is not None:
            from tpucfn.train.lora import lora_materialize

            params = jax.tree.map(np.asarray, lora_materialize(
                params, lora_adapters, scale=lora_scale))
        model = Llama(dcfg, decode=True,
                      attention_fn=serve_decode_attention_fn(cache_len))
        return cls(model, params, max_batch=max_batch, cache_len=cache_len,
                   rng=rng, prefill_width=prefill_width)

    # -- jitted bodies -----------------------------------------------------
    def _apply_one(self, params, cache_row, tokens_row):
        """One slot's apply: tokens (1, S) against its own cache row."""
        logits, muts = self.model.apply(
            {"params": params, "cache": cache_row}, tokens_row,
            mutable=["cache"])
        return logits, muts["cache"]

    def _prefill_many_impl(self, cache, temps, params, prompts, true_lens,
                           starts, slots, new_temps, key):
        """prompts (K, bucket) int32; true_lens/starts/slots (K,) int32;
        new_temps (K,) f32.  Lane k runs its tokens at cache positions
        [starts[k], starts[k] + bucket) of slot slots[k]'s row and ends
        with cache_index = true_lens[k]."""
        rows = jax.tree.map(lambda leaf: leaf[slots], cache)

        def one(row, prompt, true_len, start):
            row = _set_cache_index(row, start)
            logits, row = self._apply_one(params, row, prompt[None])
            row = _set_cache_index(row, true_len)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], true_len - start - 1, axis=0, keepdims=False)
            return row, last.astype(jnp.float32)

        rows, lasts = jax.vmap(one)(rows, prompts, true_lens, starts)
        toks = _sample(lasts, new_temps, key)
        # Duplicate pad lanes scatter identical rows — order-independent.
        new_cache = jax.tree.map(lambda full, r: full.at[slots].set(r),
                                 cache, rows)
        return toks, new_cache, temps.at[slots].set(new_temps)

    def _decode_impl(self, cache, params, tokens, temps, key):
        """tokens (B,) int32 -> (next (B,), cache).  Every slot steps."""

        def one(cache_row, tok):
            logits, row = self._apply_one(params, cache_row, tok[None, None])
            return logits[0, -1], row

        logits, new_cache = jax.vmap(one)(cache, tokens)
        return _sample(logits.astype(jnp.float32), temps, key), new_cache

    def _verify_impl(self, cache, params, tokens, temps, key):
        """tokens (B, W) int32 -> (out (B, W) int32, cache).  Every slot
        scores all W positions in one pass: out[:, 0] is sampled exactly
        as ``_decode_impl`` samples (bit-identical for greedy — the
        propose-verify correctness anchor), out[:, 1:] is greedy argmax
        (speculative acceptance is defined for greedy decode only).

        The pass is a PEEK: K/V rows gain the W new entries but every
        ``cache_index`` is restored to its pre-verify value before the
        cache is returned — the caller then ADVANCES accepted slots via
        :meth:`rollback`.  Restoring inside the program matters for the
        slots NOT in the round: a free slot's residue still backs
        prefix-cache hits, and letting its index creep up by W per
        round would eventually clamp this pass's writes back INTO the
        residue region (``dynamic_update_slice`` clamps at capacity) —
        corrupting bytes the scheduler still points at."""

        def one(cache_row, toks):
            logits, row = self._apply_one(params, cache_row, toks[None])
            return logits[0], row

        logits, new_cache = jax.vmap(one)(cache, tokens)

        def keep_index(path, new, old):
            if _path_str(path).endswith("cache_index"):
                return old
            return new

        new_cache = jax.tree_util.tree_map_with_path(
            keep_index, new_cache, cache)
        logits = logits.astype(jnp.float32)
        first = _sample(logits[:, 0], temps, key)
        rest = jnp.argmax(logits[:, 1:], axis=-1).astype(jnp.int32)
        return jnp.concatenate([first[:, None], rest], axis=1), new_cache

    def _rollback_impl(self, cache, lens, mask):
        """Set ``cache_index`` of masked slots to ``lens``; unmasked
        slots (vacant, or free slots backing prefix hits with residue)
        keep theirs.  K/V past the new index is dead by the standard
        write-before-read argument."""

        def fix(path, leaf):
            if _path_str(path).endswith("cache_index"):
                shape = (-1,) + (1,) * (leaf.ndim - 1)
                tgt = jnp.broadcast_to(
                    lens.reshape(shape), leaf.shape).astype(leaf.dtype)
                m = jnp.broadcast_to(mask.reshape(shape), leaf.shape)
                return jnp.where(m, tgt, leaf)
            return leaf

        return jax.tree_util.tree_map_with_path(fix, cache)

    def _copy_prefix_impl(self, cache, src, dst, n):
        """Plant slot ``src``'s row into slot ``dst`` with cache_index
        ``n``: the whole K/V row is copied (cheap contiguous gather/
        scatter, no length-dependent shapes -> one compile), and every
        byte past position ``n`` is dead on arrival — the suffix prefill
        or the next decode step overwrites position ``n`` before any
        query could attend past it."""

        def fix(path, leaf):
            if _path_str(path).endswith("cache_index"):
                return leaf.at[dst].set(
                    jnp.full(leaf.shape[1:], n, leaf.dtype))
            return leaf.at[dst].set(leaf[src])

        return jax.tree_util.tree_map_with_path(fix, cache)

    # -- host API (the scheduler loop calls these) -------------------------
    def _next_key(self) -> jax.Array:
        self._step_count += 1
        return jax.random.fold_in(self._base_key, self._step_count)

    def prefill(self, slot: int, prefix: list[int], bucket: int,
                temperature: float = 0.0, start: int = 0) -> int:
        """Run one bucketed prefill into ``slot``; returns the sequence's
        first sampled token.  ``start > 0`` continues from a prefix that
        :meth:`copy_prefix` already planted (``prefix`` is then the
        SUFFIX tokens only)."""
        return self.prefill_batch([(slot, prefix, start, temperature)],
                                  bucket)[slot]

    def prefill_batch(self, items, bucket: int) -> dict[int, int]:
        """One vmapped prefill over up to ``prefill_width`` sequences
        sharing ``bucket``.  ``items`` is a list of ``(slot, tokens,
        start, temperature)`` — ``tokens`` are the tokens to run (the
        suffix when ``start > 0``).  Returns {slot: first token}."""
        k = self.prefill_width
        if not 1 <= len(items) <= k:
            raise ValueError(
                f"{len(items)} prefill items vs prefill_width {k}")
        slots = [it[0] for it in items]
        if len(set(slots)) != len(slots):
            raise ValueError(f"duplicate slots in prefill batch: {slots}")
        padded = list(items) + [items[0]] * (k - len(items))
        prompts = np.zeros((k, bucket), np.int32)
        true_lens = np.zeros((k,), np.int32)
        starts = np.zeros((k,), np.int32)
        slot_arr = np.zeros((k,), np.int32)
        temps = np.zeros((k,), np.float32)
        for i, (slot, toks, start, temp) in enumerate(padded):
            n = len(toks)
            if not 1 <= n <= bucket:
                raise ValueError(
                    f"suffix len {n} / bucket {bucket} violate "
                    "1 <= len <= bucket")
            if start < 0 or start + bucket > self.cache_len:
                raise ValueError(
                    f"start {start} + bucket {bucket} exceeds cache_len "
                    f"{self.cache_len}")
            if not 0 <= slot < self.max_batch:
                raise ValueError(f"slot {slot} out of range")
            prompts[i, :n] = np.asarray(toks, np.int32)
            true_lens[i] = start + n
            starts[i] = start
            slot_arr[i] = slot
            temps[i] = temp
        toks_out, self.cache, self._temps = self._prefill_jit(
            self.cache, self._temps, self.params, jnp.asarray(prompts),
            jnp.asarray(true_lens), jnp.asarray(starts),
            jnp.asarray(slot_arr), jnp.asarray(temps), self._next_key())
        toks_out = np.asarray(toks_out)
        return {slot: int(toks_out[i]) for i, slot in enumerate(slots)}

    def copy_prefix(self, src_slot: int, dst_slot: int,
                    n_tokens: int) -> None:
        """Device-side prefix reuse: make slot ``dst_slot`` start life
        with the first ``n_tokens`` of slot ``src_slot``'s cache (a
        prefix-cache hit's replacement for re-prefilling those tokens)."""
        if not 0 <= src_slot < self.max_batch \
                or not 0 <= dst_slot < self.max_batch:
            raise ValueError(
                f"slots {src_slot}->{dst_slot} out of range "
                f"[0, {self.max_batch})")
        if src_slot == dst_slot:
            raise ValueError(f"copy_prefix onto itself (slot {src_slot})")
        if not 1 <= n_tokens <= self.cache_len:
            raise ValueError(
                f"n_tokens {n_tokens} outside [1, {self.cache_len}]")
        self.cache = self._copy_prefix_jit(
            self.cache, jnp.int32(src_slot), jnp.int32(dst_slot),
            jnp.int32(n_tokens))

    def decode(self, tokens_by_slot: dict[int, int]) -> dict[int, int]:
        """One decode iteration.  ``tokens_by_slot`` maps ACTIVE slots to
        their last emitted token; vacant slots run dead lanes.  Returns
        the next token per active slot."""
        toks = np.zeros((self.max_batch,), np.int32)
        for slot, tok in tokens_by_slot.items():
            toks[slot] = tok
        nxt, self.cache = self._decode_jit(
            self.cache, self.params, jnp.asarray(toks),
            self._temps, self._next_key())
        nxt = np.asarray(nxt)
        return {slot: int(nxt[slot]) for slot in tokens_by_slot}

    def _ensure_spec_jits(self) -> None:
        if self._verify_jit is None:
            self._verify_jit = _maybe_warm(
                jax.jit(self._verify_impl, donate_argnums=(0,)),
                "serve_verify")
            self._rollback_jit = _maybe_warm(
                jax.jit(self._rollback_impl, donate_argnums=(0,)),
                "serve_rollback")

    def verify(self, tokens_by_slot: dict[int, list[int]],
               width: int) -> dict[int, list[int]]:
        """One multi-token verify dispatch: each ACTIVE slot's row is
        its last emitted token plus ``width - 1`` proposed tokens, all
        padded to the fixed ``width`` (one compile per width).  Returns
        the target model's ``width`` next-token verdicts per active
        slot; vacant slots run dead lanes.  The pass is a PEEK: K/V
        rows gain the ``width`` new entries but every ``cache_index``
        comes back unchanged (see ``_verify_impl`` for why that is
        load-bearing) — the caller then ADVANCES each active slot to
        its accepted length via :meth:`rollback` before the next engine
        call touches it."""
        if width < 1:
            raise ValueError(f"verify width must be >= 1, got {width}")
        self._ensure_spec_jits()
        toks = np.zeros((self.max_batch, width), np.int32)
        for slot, run in tokens_by_slot.items():
            if len(run) != width:
                raise ValueError(
                    f"slot {slot}: run of {len(run)} tokens vs width "
                    f"{width}")
            toks[slot] = np.asarray(run, np.int32)
        out, self.cache = self._verify_jit(
            self.cache, self.params, jnp.asarray(toks), self._temps,
            self._next_key())
        out = np.asarray(out)
        return {slot: [int(t) for t in out[slot]]
                for slot in tokens_by_slot}

    def rollback(self, lengths_by_slot: dict[int, int]) -> None:
        """Repair ``cache_index`` after a verify (or a draft's proposal
        run) over-advanced it: each listed slot's index is set to its
        accepted cache length; every other slot is untouched."""
        if not lengths_by_slot:
            return
        self._ensure_spec_jits()
        lens = np.zeros((self.max_batch,), np.int32)
        mask = np.zeros((self.max_batch,), bool)
        for slot, n in lengths_by_slot.items():
            if not 0 <= n <= self.cache_len:
                raise ValueError(
                    f"rollback length {n} outside [0, {self.cache_len}]")
            lens[slot] = n
            mask[slot] = True
        self.cache = self._rollback_jit(
            self.cache, jnp.asarray(lens), jnp.asarray(mask))

    def compile_counts(self) -> dict[str, int]:
        """Compiled-program counts per entry point — the compile-budget
        contract (len(prefill buckets) + 1 decode + 1 copy_prefix) a
        test asserts instead of trusting the docstring."""

        def n(f) -> int:
            try:
                return int(f._cache_size())
            except Exception:  # pragma: no cover - jax internals moved
                return -1

        counts = {"prefill": n(self._prefill_jit),
                  "decode": n(self._decode_jit),
                  "copy_prefix": n(self._copy_prefix_jit)}
        # Spec programs only exist once verify/rollback ran — a plain
        # engine's surface stays exactly the three entries above.
        if self._verify_jit is not None:
            counts["verify"] = n(self._verify_jit)
            counts["rollback"] = n(self._rollback_jit)
        return counts


# Named Llama configs for the demo/bench surfaces (one source of truth
# for `tpucfn serve --preset` and `benches/serve_bench.py`).  "nano" is
# the draft-model demo size (ISSUE 14): a deliberately-smaller decoder
# for `--spec-draft` whose per-step cost is a fraction of tiny's.
LLAMA_PRESETS = ("nano", "tiny", "llama3-1b", "llama3-8b")


def _nano_config():
    import dataclasses as _dc

    from tpucfn.models.llama import LlamaConfig

    return _dc.replace(LlamaConfig.tiny(), dim=32, n_layers=1, n_heads=2,
                       n_kv_heads=1, ffn_dim=64)


def demo_llama_engine(preset: str, *, seed: int = 0, max_batch: int = 8,
                      cache_len: int | None = None, prefill_width: int = 4):
    """(cfg, ServeEngine) over a RANDOM-init Llama preset — the shared
    bring-up for the CLI demo workload and the serving bench (real
    deployments construct the engine from checkpointed params
    themselves)."""
    import jax

    from tpucfn.models.llama import Llama, LlamaConfig

    ctors = {"nano": _nano_config, "tiny": LlamaConfig.tiny,
             "llama3-1b": LlamaConfig.llama3_1b,
             "llama3-8b": LlamaConfig.llama3_8b}
    cfg = ctors[preset]()
    params = Llama(cfg).init(jax.random.key(seed),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, ServeEngine.from_llama(cfg, params, max_batch=max_batch,
                                       cache_len=cache_len,
                                       prefill_width=prefill_width)
