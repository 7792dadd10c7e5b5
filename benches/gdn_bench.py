#!/usr/bin/env python
"""The gated delta rule alone, both of its preparations, on whatever backend
jax selects (a number means something only on the chip).

Times ``tpucfn.ops.gated_delta.gated_delta_rule`` forward and forward +
backward at the shapes of the benchmark cell ``qwen3next-ep8-s8192`` (2 x 8,192
positions, 16 key / 32 value heads of 128, bfloat16, chunk 64) through the
``jnp`` preparation and through the Pallas kernel pair, and prints one JSON
line each:

    {"path": "kernel", "pass": "fwd_bwd", "pallas": true, "median_ms": ...,
     "device": ...}

The path is chosen as the program chooses it, from the backend it is told:
the ``jnp`` rows answer ``cpu`` to that question, nothing else differs;
``pallas`` says whether the kernel pair ran (off a TPU both rows are ``jnp``'s).
No cell of the benchmark runs this tool.

Usage (on a TPU host):  python benches/gdn_bench.py [--seq 8192 ...]; the
shapes of ``olmohybrid-gdn-s16384``: ``--batch 1 --seq 16384 --key-heads 30
--value-heads 30 --head-dim 96 --value-dim 192``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def median_ms(fn, *args, iters: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))      # compile and warm
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--key-heads", type=int, default=16)
    p.add_argument("--value-heads", type=int, default=32)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--value-dim", type=int, default=None,
                   help="the values' head size where it is not --head-dim")
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from tpucfn.ops import gated_delta

    device = jax.devices()[0].device_kind
    ks = jax.random.split(jax.random.key(0), 5)
    b, s, hk, hv, d = (args.batch, args.seq, args.key_heads, args.value_heads,
                       args.head_dim)
    dv = args.value_dim or d

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = (unit(jax.random.normal(ks[0], (b, s, hk, d))) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (b, s, hk, d))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, hv, dv), jnp.bfloat16)
    g = -jax.random.uniform(ks[3], (b, s, hv), minval=0.0, maxval=0.1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))

    backend = gated_delta._backend
    for path in ("jnp", "kernel"):
        # each path is traced under its own answer to the backend question,
        # through functions of its own: jit keys its traces by the function
        gated_delta._backend = (lambda: "cpu") if path == "jnp" else backend

        def rule(*a):
            return gated_delta.gated_delta_rule(*a, chunk_size=args.chunk)[0]

        def loss(*a):
            return jnp.sum(rule(*a).astype(jnp.float32) ** 2)

        passes = {"fwd": jax.jit(rule),
                  "fwd_bwd": jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))}
        for name, fn in passes.items():
            print(json.dumps({
                "path": path, "pass": name,
                "pallas": gated_delta._kernel_serves(jnp.bfloat16, args.chunk),
                "median_ms": round(median_ms(fn, q, k, v, g, beta,
                                             iters=args.iters), 3),
                "batch": b, "seq": s, "key_heads": hk, "value_heads": hv,
                "head_dim": d, "value_dim": dv, "chunk": args.chunk,
                "device": device}),
                flush=True)
    gated_delta._backend = backend
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
