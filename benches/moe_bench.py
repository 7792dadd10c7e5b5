#!/usr/bin/env python
"""The routed expert layer alone, at three loads, on whatever backend jax
selects (a number means something only on the chip).

Times ``tpucfn.models.moe.RoutedExperts`` forward and forward + backward
(gradients of its parameters and its input) at the shapes of the benchmark's
two sparse cells, with the router shifted so that the rows which fall on the
held experts fill half a block (the load of a balanced router: one block
runs), a block and a half (two run) and every block (every assignment falls
here), and prints one JSON line each:

    {"preset": "joyai-mla-s8192", "load": "one_block", "pass": "fwd_bwd",
     "median_ms": ..., "rows": 8192, "blocks_run": 1, "blocks": 8,
     "block": 16384, "device": ...}

``rows`` and ``blocks_run`` are the layer's own counters.  A block that holds
no row should cost nothing: the three loads' times differ by the blocks that
ran.  No cell of the benchmark runs this tool.

Usage (on a TPU host):  python benches/moe_bench.py [--preset joyai-mla-s8192]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benches.gdn_bench import median_ms  # noqa: E402

# The two cells whose feed-forward is this layer (benchmark/configs/*.json and
# benchmark/traffic/*.json hold the sources): a chip's share of the experts,
# the router at its published width.
PRESETS = {
    "joyai-mla-s8192": dict(
        tokens=16384, dim=2048, experts=256, held=16, ffn_dim=768, top_k=8,
        shared_dim=768, score="sigmoid", select_bias=True, weight_scale=2.5,
        shared_gate=False),
    "qwen3next-ep8-s8192": dict(
        tokens=16384, dim=2048, experts=512, held=64, ffn_dim=512, top_k=10,
        shared_dim=512, score="softmax", select_bias=False, weight_scale=1.0,
        shared_gate=True),
}

# load -> the share of a block's rows the held experts are sent (None: all)
LOADS = {"one_block": 0.5, "two_blocks": 1.5, "all_blocks": None}


def shifted(params, x, shape, shift):
    """The router's logits of the held experts raised by ``shift`` for every
    token, through a feature that is 1 in all of them."""
    router = params["router"]["kernel"]
    params = dict(params, router={"kernel": router.at[0, :shape["held"]].add(shift)})
    return params, x.at[:, 0].set(1.0)


def shift_for(rows_of, want: int) -> float:
    """The shift at which about ``want`` assignments fall on held experts:
    their count rises with it, so bisect."""
    lo, hi = -30.0, 30.0
    for _ in range(24):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if rows_of(mid) < want else (lo, mid)
    return hi


def rows(preset: str, shape: dict, iters: int):
    import jax
    import jax.numpy as jnp

    from tpucfn.models.moe import RoutedExperts

    layer = RoutedExperts(
        shape["experts"], shape["top_k"], shape["ffn_dim"], (0, shape["held"]),
        shared_dim=shape["shared_dim"], dtype=jnp.bfloat16, score=shape["score"],
        select_bias=shape["select_bias"], weight_scale=shape["weight_scale"],
        shared_gate=shape["shared_gate"])
    t, n = shape["tokens"], shape["tokens"] * shape["top_k"]
    block = min(n, -(-2 * n * shape["held"] // shape["experts"]))
    x = jax.random.normal(jax.random.key(1), (t, shape["dim"]), jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.key(0), x)["params"]

    def apply(p, x):
        return layer.apply({"params": p}, x)

    def loss(p, x):
        return jnp.sum(apply(p, x)[0].astype(jnp.float32) ** 2)

    stats_of = jax.jit(lambda p, x: apply(p, x)[1])
    passes = {"fwd": jax.jit(lambda p, x: apply(p, x)[0]),
              "fwd_bwd": jax.jit(jax.grad(loss, argnums=(0, 1)))}
    for load, share in LOADS.items():
        # 12 above logits of deviation under 1: every choice a held expert,
        # their scores still apart
        shift = 12.0 if share is None else shift_for(
            lambda s: float(stats_of(*shifted(params, x, shape, s))["rows"]),
            round(share * block))
        p, xs = shifted(params, x, shape, shift)
        stats = stats_of(p, xs)
        for name, fn in passes.items():
            yield {"preset": preset, "load": load, "pass": name,
                   "median_ms": round(median_ms(fn, p, xs, iters=iters), 3),
                   "rows": int(stats["rows"]),
                   "blocks_run": int(stats["blocks_run"]),
                   "dropped": int(stats["dropped"]),
                   "blocks": -(-n // block), "block": block,
                   **{k: shape[k] for k in ("tokens", "dim", "held", "experts",
                                            "ffn_dim", "top_k")},
                   "device": jax.devices()[0].device_kind}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", nargs="*", choices=sorted(PRESETS),
                   default=sorted(PRESETS))
    p.add_argument("--tokens", type=int, help="in place of the preset's")
    p.add_argument("--dim", type=int, help="in place of the preset's")
    p.add_argument("--ffn-dim", type=int, help="in place of the preset's")
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()
    for preset in args.preset:
        given = {"tokens": args.tokens, "dim": args.dim,
                 "ffn_dim": args.ffn_dim, "shared_dim": args.ffn_dim}
        shape = {**PRESETS[preset],
                 **{k: v for k, v in given.items() if v is not None}}
        for row in rows(preset, shape, args.iters):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
