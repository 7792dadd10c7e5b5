#!/usr/bin/env python
"""The chunked state-space op alone, beside its roofline, on whatever backend
jax selects (a number means something only on the chip).

Times ``tpucfn.ops.ssd.ssd`` forward and forward + backward at the shape of the
benchmark cell ``granite4h-ssd-s16384`` (``--preset``, the default: 1 x 16,384
positions, 64 heads of 64, one group of state 128, chunk 256, bfloat16; any
size can be given), through the ``jnp`` form and through the path the model
takes (the Pallas kernels of ``tpucfn/kernels/ssd.py`` where the op's rule
says so), and prints one JSON line a path and pass:

    {"preset": "granite4h-ssd-s16384", "path": "model", "pass": "fwd_bwd",
     "pallas": true, "median_ms": ..., "least_ms": ..., "bound": "compute",
     "roofline_pct": ..., "device": ...}

The path is chosen as the program chooses it, from the backend it is told: the
``jnp`` rows answer ``cpu`` to that question, nothing else differs; ``pallas``
says whether the kernels ran (off a TPU both paths are ``jnp``'s).
``least_ms`` is ``benchmark/flops_granite4_h.ssd_call`` through
``benchmark/peaks.json``: the yardstick of the cell's ``ssd_roofline.g4h``
(forward + backward: one pass of each, as the metric counts a step's).  What a
Mamba layer pays a step is two forward passes (the second the layer's
rematerialisation) and one backward.  No cell of the benchmark runs this tool.

Usage (on a TPU host):  python benches/ssd_bench.py [--chunk 128 ...]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benches.gdn_bench import median_ms  # noqa: E402

# benchmark/configs/granite-4.0-h-micro.json and benchmark/traffic/b1-s16384.json
PRESETS = {
    "granite4h-ssd-s16384": dict(batch=1, seq=16384, heads=64, head_dim=64,
                                 state=128, groups=1, chunk=256),
}


def least_ms(shape: dict, passes, peak: dict) -> tuple[float, str]:
    from benchmark import flops, flops_granite4_h

    m = {"mamba_n_heads": shape["heads"], "mamba_d_head": shape["head_dim"],
         "mamba_d_state": shape["state"], "mamba_n_groups": shape["groups"],
         "mamba_chunk_size": shape["chunk"]}
    found = [flops.roofline_seconds(*flops_granite4_h.ssd_call(
        kind, shape["batch"], shape["seq"], m), peak) for kind in passes]
    return 1e3 * sum(t for t, _ in found), found[-1][1]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", choices=sorted(PRESETS),
                   default="granite4h-ssd-s16384")
    for name in ("batch", "seq", "heads", "head-dim", "state", "groups", "chunk"):
        p.add_argument(f"--{name}", type=int, default=None)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()
    shape = {k: getattr(args, k) or v for k, v in PRESETS[args.preset].items()}

    import jax
    import jax.numpy as jnp

    from tpucfn.ops import ssd as ssd_op

    device = jax.devices()[0].device_kind
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    b, s, h, p_, n, g = (shape[k] for k in ("batch", "seq", "heads", "head_dim",
                                            "state", "groups"))
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p_), jnp.bfloat16)
    # the published initialiser's ranges: dt log-uniform on (1e-3, 1e-1), A on (1, 16)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, s, h), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    bm, cm = (jax.random.normal(k, (b, s, g, n), jnp.bfloat16) for k in ks[3:])
    d = jnp.ones((h,))

    backend = ssd_op._backend
    for path in ("jnp", "model"):
        # each path is traced under its own answer to the backend question,
        # through functions of its own: jit keys its traces by the function
        ssd_op._backend = (lambda: "cpu") if path == "jnp" else backend
        pallas = ssd_op._kernel_serves(jnp.bfloat16, shape["chunk"], n, h // g, p_)

        def op(*t):
            return ssd_op.ssd(*t, chunk_size=shape["chunk"])[0]

        def loss(*t):
            return jnp.sum(op(*t).astype(jnp.float32) ** 2)

        passes = {"fwd": (jax.jit(op), ("fwd",)),
                  "fwd_bwd": (jax.jit(jax.grad(loss, argnums=tuple(range(6)))),
                              ("fwd", "bwd"))}
        for name, (fn, counted) in passes.items():
            row = {"preset": args.preset, "path": path, "pass": name,
                   "pallas": pallas,
                   "median_ms": round(median_ms(fn, x, dt, a, bm, cm, d,
                                                iters=args.iters), 3)}
            if device in peaks:   # off the chip there is no roofline to stand beside
                least, bound = least_ms(shape, counted, peaks[device])
                row.update(least_ms=round(least, 3), bound=bound,
                           roofline_pct=round(100 * least / row["median_ms"], 2))
            print(json.dumps({**row, **shape, "device": device}), flush=True)
    ssd_op._backend = backend
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
