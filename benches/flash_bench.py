#!/usr/bin/env python
"""Flash-attention micro-benchmark: each kernel beside its roofline, and
the kernels together against XLA's dense attention.

Two modes, both meaningful only on the real chip (a CPU run times the
Pallas interpreter):

``--preset <cell>`` (the shapes of the benchmark's flash cells) times
the three kernels one at a time on (B, H, S, D) arrays, as the train step
calls them, and prints one JSON line:

    {"preset": ..., "blocks": [256, 512],
     "steps": {"interior": 240, "edge": 32, "skipped": 240},
     "fwd": {"ms": ..., "least_ms": ..., "roofline_pct": ...},
     "dkv": {...}, "dq": {...}}

``least_ms`` is ``benchmark/flops.flash_call`` through
``benchmark/peaks.json``: the yardstick of the cells' ``flash_roofline``.
``steps`` is the static count of one head's grid steps by class for the
chosen blocks: how often the unmasked body runs, and how many steps only
pay a grid step's fixed cost.

``--seqs ...`` (the older mode) measures forward and forward+backward
wall time of the public wrapper against dense, one JSON line per S:

    {"s": 8192, "fwd_flash_ms": ..., "fwd_dense_ms": ...,
     "bwd_flash_ms": ..., "bwd_dense_ms": ..., "speedup_fwd": ...}

Usage (on a TPU host):  python benches/flash_bench.py --preset mistral7b-s8192
Block tuning: TPUCFN_FLASH_BLOCK_Q/_K or --block-q/--block-k sweeps.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# The cells whose attention runs through the kernels
# (benchmark/configs/*.json and benchmark/traffic/*.json hold the sources).
PRESETS = {
    "mistral7b-s8192": dict(batch=1, seq=8192, heads=32, kv_heads=8,
                            head_dim=128),
    "qwen3next-ep8-s8192": dict(batch=2, seq=8192, heads=16, kv_heads=2,
                                head_dim=256),
    # latent attention: keys of 192 (128 + 64 rotary), values of 128
    "joyai-mla-s8192": dict(batch=2, seq=8192, heads=32, kv_heads=32,
                            head_dim=192, value_dim=128),
    # heads of 64, half the lanes (the model's own scale changes no time)
    "granite4h-ssd-s16384": dict(batch=1, seq=16384, heads=32, kv_heads=8,
                                 head_dim=64),
}


def _time(fn, *args, iters=10):
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm (pytree-safe)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def kernel_times(batch, seq, heads, kv_heads, head_dim, value_dim=None, *,
                 blocks=None, iters=10) -> dict:
    """ms a call of each kernel at this shape, causal, bfloat16, with the
    blocks the wrapper would choose (or ``blocks``), and the grid steps of
    one head by class."""
    import jax
    import jax.numpy as jnp

    # the package re-exports the function under the module's name
    fa = importlib.import_module("tpucfn.kernels.flash_attention")

    interpret = jax.default_backend() != "tpu"  # a rehearsal, not a timing
    value_dim = value_dim or head_dim
    block_q, block_k = blocks or fa._choose_blocks(
        seq, head_dim, jnp.bfloat16, True, value_dim)
    grid = fa._Grid(True, block_q, block_k, 0, 0, seq, seq, False)
    scale = head_dim ** -0.5
    keys = jax.random.split(jax.random.key(0), 4)
    q, do, k, v = (
        jax.random.normal(key, (batch, h, seq, d), jnp.bfloat16)
        for key, h, d in zip(keys, (heads, heads, kv_heads, kv_heads),
                             (head_dim, value_dim, head_dim, value_dim)))

    fwd = jax.jit(lambda q, k, v: fa._flash_fwd(
        q, k, v, None, None, causal=True, q_offset=0, k_offset=0, kv_len=seq,
        block_sizes=(block_q, block_k), interpret=interpret, scale=scale))
    o, lse = fwd(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = jax.jit(lambda *a: fa._flash_dq(*a, None, None, grid=grid,
                                         interpret=interpret, scale=scale))
    dkv = jax.jit(lambda *a: fa._flash_dkv(*a, None, None, grid=grid,
                                           interpret=interpret, scale=scale))
    return {
        "blocks": [block_q, block_k],
        "steps": grid.count(seq),
        "fwd": _time(fwd, q, k, v, iters=iters),
        "dkv": _time(dkv, q, k, v, do, fa._sublanes(lse[..., 0]),
                     fa._sublanes(delta), iters=iters),
        "dq": _time(dq, q, k, v, do, lse, fa._lanes(delta), iters=iters),
    }


def beside_roofline(shape: dict, times: dict, peak: dict) -> dict:
    """Each kernel's ms a call beside the least the chip could take for the
    call's shape (``benchmark/flops.flash_call``, the cells' yardstick)."""
    from benchmark import flops, flops_joyai_llm_flash

    row = {**shape, "blocks": times["blocks"], "steps": times["steps"]}
    for kind in ("fwd", "dkv", "dq"):
        # equal head sizes count as ``flops.flash_call`` does
        least, bound = flops.roofline_seconds(*flops_joyai_llm_flash.flash_call(
            kind, shape["batch"], shape["seq"], shape["heads"],
            shape["kv_heads"], shape["head_dim"],
            shape.get("value_dim", shape["head_dim"])), peak)
        row[kind] = {"ms": round(times[kind], 3),
                     "least_ms": round(least * 1e3, 3), "bound": bound,
                     "roofline_pct": round(100 * least * 1e3 / times[kind], 1)}
    return row


def preset_row(name: str, *, blocks=None, iters=10) -> dict:
    import jax

    peak = json.loads((ROOT / "benchmark" / "peaks.json").read_text())[
        jax.devices()[0].device_kind]  # an unknown device is an error
    shape = PRESETS[name]
    times = kernel_times(**shape, blocks=blocks, iters=iters)
    return {"preset": name, **beside_roofline(shape, times, peak)}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", choices=sorted(PRESETS), nargs="+",
                   help="time each kernel at a benchmark cell's shape")
    p.add_argument("--seqs", type=int, nargs="+", default=[2048, 8192, 32768])
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--block-q", type=int, default=None)
    p.add_argument("--block-k", type=int, default=None)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from tpucfn.kernels import flash_attention
    from tpucfn.ops.attention import dot_product_attention

    print(f"# backend={jax.default_backend()} "
          f"device={jax.devices()[0].device_kind}", file=sys.stderr)

    if args.preset:
        blocks = None
        if args.block_q and args.block_k:
            blocks = (args.block_q, args.block_k)
        for name in args.preset:
            print(json.dumps(preset_row(name, blocks=blocks,
                                        iters=args.iters)), flush=True)
        return 0

    for s in args.seqs:
        rs = jax.random.key(0)
        kq, kk, kv = jax.random.split(rs, 3)
        shape_q = (args.batch, s, args.heads, args.head_dim)
        shape_kv = (args.batch, s, args.kv_heads, args.head_dim)
        q = jax.random.normal(kq, shape_q, jnp.bfloat16)
        k = jax.random.normal(kk, shape_kv, jnp.bfloat16)
        v = jax.random.normal(kv, shape_kv, jnp.bfloat16)

        flash = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=args.block_q, block_k=args.block_k))
        dense = jax.jit(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True))

        def g(fn):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2)))

        row = {"s": s, "heads": args.heads, "kv_heads": args.kv_heads,
               "d": args.head_dim}
        row["fwd_flash_ms"] = round(_time(flash, q, k, v, iters=args.iters), 3)
        try:
            row["fwd_dense_ms"] = round(
                _time(dense, q, k, v, iters=args.iters), 3)
        except Exception as e:  # dense S=32k logits can OOM — that's the point
            row["fwd_dense_ms"] = None
            row["dense_error"] = type(e).__name__
        row["bwd_flash_ms"] = round(
            _time(g(flash), q, k, v, iters=args.iters), 3)
        if row["fwd_dense_ms"] is not None:
            row["bwd_dense_ms"] = round(
                _time(g(dense), q, k, v, iters=args.iters), 3)
            row["speedup_fwd"] = round(
                row["fwd_dense_ms"] / row["fwd_flash_ms"], 2)
            row["speedup_bwd"] = round(
                row["bwd_dense_ms"] / row["bwd_flash_ms"], 2)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
