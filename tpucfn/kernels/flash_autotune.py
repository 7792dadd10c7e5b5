"""Flash-attention block-size autotuner + persisted best-config table.

VERDICT r2 item 3: block sizes were a fixed 128/128 with env overrides
and no way to learn better ones. This module adds the missing piece:

* :func:`tune` — eagerly times candidate (block_q, block_k) pairs for a
  given (S, D, dtype, causal) ON THE CURRENT BACKEND (fwd + bwd, real
  executions — must run outside jit) and persists the winner.
* :func:`lookup` — consulted by ``flash_attention``'s wrapper at trace
  time (pure dict read): explicit ``block_q/block_k`` args win, then
  ``TPUCFN_FLASH_BLOCK_Q/_K`` env overrides, then this table, then the
  128/128 default.

The table is keyed by (device_kind, causal, S-bucket, D, dtype) where
the S bucket is the next power of two — one tuning run covers the
nearby shape family. ``D`` is the head size; where the values are
narrower or wider than the keys (latent attention: 192 / 128) it reads
``<D>v<DV>``, so the rows of equal sizes keep their names. Cache file: ``~/.tpucfn/flash_tune.json``
(``TPUCFN_FLASH_TUNE_CACHE`` overrides; delete it to re-tune).

The reference delegated this entirely to cuDNN's internal heuristics
(SURVEY.md §2.2 CUDA/cuDNN row); on TPU the block shape is ours to
pick, and the best pick is device-generation- and shape-dependent.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

_MEM_CACHE: dict[str, tuple[int, int]] | None = None

DEFAULT_CANDIDATES = ((128, 128), (128, 256), (256, 128), (256, 256),
                      (128, 512), (512, 128), (256, 512), (512, 256),
                      (512, 512), (512, 1024), (1024, 512), (1024, 1024))


def _cache_path() -> Path:
    return Path(os.environ.get(
        "TPUCFN_FLASH_TUNE_CACHE",
        os.path.expanduser("~/.tpucfn/flash_tune.json")))


def _bucket(s: int) -> int:
    b = 128
    while b < s:
        b *= 2
    return b


def _key(device_kind: str, causal: bool, s: int, d: int, dtype,
         dv: int | None = None) -> str:
    import numpy as np

    head = str(d) if dv in (None, d) else f"{d}v{dv}"
    return "|".join([device_kind, "causal" if causal else "full",
                     str(_bucket(s)), head, str(np.dtype(dtype))])


def _read_table(path: Path) -> dict[str, tuple]:
    """Entries are [block_q, block_k] (legacy) or [block_q, block_k,
    speedup] where speedup is the MEASURED fwd+bwd dense/flash time
    ratio at tune time (None/absent = never measured against dense)."""
    try:
        raw = json.loads(path.read_text())
        return {k: tuple(v) for k, v in raw.items()}
    except (OSError, ValueError):
        return {}


def _load() -> dict[str, tuple[int, int]]:
    """User cache layered over the packaged table: tunes shipped with the
    repo (flash_tune_builtin.json — measured on real chips, see PARITY
    round-3 status) seed the defaults; a user's own ``tune`` runs
    override them per key.  The user cache file stores only the user's
    own tunes (``_save`` never writes builtin entries into it, so a
    package update can improve unpinned keys)."""
    global _MEM_CACHE
    if _MEM_CACHE is None:
        table = _read_table(Path(__file__).parent / "flash_tune_builtin.json")
        for k, v in _read_table(_cache_path()).items():
            # A legacy (pre-speedup) user entry must not erase a builtin
            # measured ratio it agrees with on blocks — that would flip
            # a measured-winning family back to the no-evidence dense
            # rule for exactly the users who tuned.
            old = table.get(k)
            if (len(v) < 3 and old is not None and len(old) >= 3
                    and tuple(old[:2]) == tuple(v[:2])):
                v = tuple(v[:2]) + (old[2],)
            table[k] = v
        _MEM_CACHE = table
    return _MEM_CACHE


def _save(cache: dict[str, tuple[int, int]]) -> None:
    p = _cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_suffix(".tmp")
    tmp.write_text(json.dumps({k: list(v) for k, v in cache.items()},
                              indent=1, sort_keys=True))
    os.replace(tmp, p)


def _entry(s: int, d: int, dtype, causal: bool,
           dv: int | None = None) -> tuple | None:
    import jax

    kind = jax.devices()[0].device_kind
    return _load().get(_key(kind, causal, s, d, dtype, dv))


def kind_has_entries(device_kind: str) -> bool:
    """Whether the merged table (builtin + user cache) has ANY entry for
    this device kind — the discoverability probe behind
    ``kernels.auto``'s one-time untuned-device warning: a kind with zero
    entries runs dense everywhere below ``untuned_flash_min_s`` and the
    operator should know why."""
    prefix = device_kind + "|"
    return any(k.startswith(prefix) for k in _load())


def lookup(s: int, d: int, dtype, causal: bool,
           dv: int | None = None) -> tuple[int, int] | None:
    """Best known (block_q, block_k) for this shape family on the
    current device, or None. Trace-time safe (no device work). ``dv`` is
    the values' head size where it is not ``d``."""
    e = _entry(s, d, dtype, causal, dv)
    return None if e is None else tuple(e[:2])


def lookup_speedup(s: int, d: int, dtype, causal: bool,
                   dv: int | None = None) -> float | None:
    """MEASURED fwd+bwd speedup of tuned flash over XLA dense for this
    shape family on the current device — the evidence
    ``kernels.auto``'s dispatch consults (VERDICT r4 #5). None when the
    family was never tuned against dense (incl. legacy 2-entry rows)."""
    e = _entry(s, d, dtype, causal, dv)
    if e is None or len(e) < 3 or e[2] is None:
        return None
    return float(e[2])


def tune(
    s: int,
    d: int = 128,
    *,
    dv: int | None = None,
    heads: int = 8,
    kv_heads: int = 8,
    batch: int = 1,
    dtype=None,
    causal: bool = True,
    candidates=DEFAULT_CANDIDATES,
    iters: int = 5,
    include_bwd: bool = True,
    persist: bool = True,
    scale: float | None = None,
) -> dict:
    """Time each candidate block pair eagerly; persist + return results.

    Returns {"best": (bq, bk), "rows": [{blocks, fwd_ms, bwd_ms, total_ms
    | error}], "key": cache_key}. Call OUTSIDE jit, on the device you
    intend to run on (CPU runs interpret mode — only useful for testing
    the mechanism, not for real numbers).  ``scale`` is the scores'
    multiplier both paths are timed at (None: the keys' ``d ** -0.5``); the
    table's key does not hold it, a block step costs the same at any.
    """
    import jax
    import jax.numpy as jnp

    from tpucfn.kernels.flash_attention import SUBLANES, flash_attention

    dtype = dtype or jnp.bfloat16
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (batch, s, heads, d), dtype)
    k = jax.random.normal(kk, (batch, s, kv_heads, d), dtype)
    v = jax.random.normal(kv, (batch, s, kv_heads, dv or d), dtype)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    rows = []
    for bq, bk in candidates:
        if bq % SUBLANES or bk % SUBLANES or bq > s or bk > s:
            continue
        row = {"blocks": (bq, bk)}
        try:
            fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk, scale=scale))
            row["fwd_ms"] = round(timed(fwd, q, k, v), 3)
            total = row["fwd_ms"]
            if include_bwd:
                bwd = jax.jit(jax.grad(
                    lambda q, k, v, bq=bq, bk=bk: jnp.sum(flash_attention(
                        q, k, v, causal=causal, block_q=bq, block_k=bk,
                        scale=scale).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2)))
                row["bwd_ms"] = round(timed(bwd, q, k, v), 3)
                total += row["bwd_ms"]
            row["total_ms"] = round(total, 3)
        except Exception as e:  # noqa: BLE001 — e.g. VMEM overflow at 512
            row["error"] = repr(e)[:200]
        rows.append(row)

    ok = [r for r in rows if "total_ms" in r]
    if not ok:
        raise RuntimeError(f"no flash block candidate ran for S={s}, D={d}: "
                           f"{[r.get('error') for r in rows]}")
    best_row = min(ok, key=lambda r: r["total_ms"])
    best = best_row["blocks"]

    # Time XLA dense at the same shape: the dispatch policy needs the
    # dense/flash ratio, not just the best blocks (VERDICT r4 #5 — a
    # tuned-but-losing family must fall back to dense). Dense OOM at
    # long S is an answer too: speedup None = "dense not runnable",
    # which the untuned-length rule in kernels.auto resolves.
    speedup = None
    dense_ms = None
    if include_bwd:
        from tpucfn.ops.attention import dot_product_attention

        try:
            dfwd = jax.jit(lambda q, k, v: dot_product_attention(
                q, k, v, causal=causal, scale=scale))
            dense_f = timed(dfwd, q, k, v)
            dbwd = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(dot_product_attention(
                    q, k, v, causal=causal, scale=scale
                ).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2)))
            dense_ms = round(dense_f + timed(dbwd, q, k, v), 3)
            speedup = round(dense_ms / best_row["total_ms"], 3)
        except Exception as e:  # noqa: BLE001 — dense OOM at long S
            dense_ms = f"error: {repr(e)[:160]}"

    key = _key(jax.devices()[0].device_kind, causal, s, d, dtype, dv)
    if persist:
        global _MEM_CACHE
        user = _read_table(_cache_path())
        if speedup is None:
            # A speedup-less re-tune (fwd-only, or dense errored) must
            # not erase a previously MEASURED ratio it agrees with on
            # blocks — same preservation rule as the builtin merge.
            old = user.get(key)
            if (old is not None and len(old) >= 3 and old[2] is not None
                    and tuple(old[:2]) == tuple(best)):
                speedup = old[2]
        user[key] = tuple(best) + ((speedup,) if speedup is not None else ())
        _save(user)
        _MEM_CACHE = None  # re-merge (builtin + user) on next lookup
    return {"best": tuple(best), "rows": rows, "key": key,
            "dense_total_ms": dense_ms, "speedup_vs_dense": speedup}
