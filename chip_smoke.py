#!/usr/bin/env python
"""The quickest proof that tpucfn still starts on the chip.

One process drives the main paths once, through the entry points a user
calls, at the full published width of Llama-3.2-1B and ResNet-50 (random
weights from a seed), and checks what comes out by the repo's own means:

    python chip_smoke.py               # one TPU chip: kernel, train_llama,
                                       # serve, train_example
    python chip_smoke.py --four-chips  # four chips: fsdp4 only

It fails (non-zero, no result line) unless JAX's first device is a TPU;
nothing here sets ``JAX_PLATFORMS`` or retries on the CPU.  ``--rehearse``
is for tests and builders only: tiny sizes, interpreted kernel, whatever
platform JAX has — it checks the script's control flow, never the chip.

Every phase prints one JSON line (seconds, XLA compile seconds, the facts
its gates read).  A failed gate raises and ends the run at once.  The last
line is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.
Rates and byte counts printed here are smoke readings, not benchmark
results.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(ROOT, ".chip_smoke")  # listed in .gitignore
SEED = 0

# Kernel gate: flash against dense on the same bf16 inputs, per tensor,
# max|flash - dense| <= KERNEL_TOL * max|dense| (2^-5: four bf16 ulps of
# the largest magnitude — two independent bf16 roundings plus the kernel's
# different summation order; a wrong mask or block is O(1) off).
KERNEL_TOL = 2.0 ** -5
# fsdp4 gate: per-step |loss(4 chips) - loss(1 chip)| at loss ~ ln(128256)
# = 11.8; one bf16 ulp there is 0.0625, reduction order is all that differs.
FSDP_LOSS_TOL = 0.05


def gate(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"gate failed: {what}")


class Phases:
    """Times each phase and sums the XLA backend-compile seconds jax
    reports inside it (a warm persistent cache shows up here)."""

    def __init__(self):
        import jax

        self._compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += duration

    def run(self, name: str, fn, *args) -> None:
        self._compile_s = 0.0
        t0 = time.perf_counter()
        facts = fn(*args)
        print(json.dumps({"phase": name,
                          "seconds": round(time.perf_counter() - t0, 2),
                          "compile_seconds": round(self._compile_s, 2),
                          **facts}), flush=True)


# ---------------------------------------------------------------- kernel

def phase_kernel(rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpucfn.kernels import flash_autotune
    from tpucfn.kernels.flash_attention import flash_attention
    from tpucfn.ops.attention import dot_product_attention

    b, s, h, hkv, d = (1, 256, 4, 2, 64) if rehearse else (2, 2048, 32, 8, 128)
    blocks = flash_autotune.lookup(s, d, jnp.bfloat16, True)
    gate(rehearse or blocks is not None,
         f"no committed tune-table row for S={s} D={d} bf16 causal on "
         f"{jax.devices()[0].device_kind!r}")
    bq, bk = blocks or (128, 128)

    kq, kk, kv, kw = jax.random.split(jax.random.key(SEED), 4)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)
    w = jax.random.normal(kw, (b, s, h, d), jnp.bfloat16)

    def fwd_bwd(attn):
        def f(q, k, v, w):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out

        def run(q, k, v, w):
            (_, out), grads = jax.value_and_grad(
                f, argnums=(0, 1, 2), has_aux=True)(q, k, v, w)
            return (out, *grads)

        return jax.jit(run)

    flash = fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk,
        interpret=rehearse)).lower(q, k, v, w).compile()
    dense = fwd_bwd(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True))
    n_kernels = flash.as_text().count("tpu_custom_call")
    gate(rehearse or n_kernels > 0, "flash program holds no tpu_custom_call")

    jax.block_until_ready(flash(q, k, v, w))
    t0 = time.perf_counter()
    got = jax.block_until_ready(flash(q, k, v, w))
    flash_s = time.perf_counter() - t0
    ref = jax.block_until_ready(dense(q, k, v, w))

    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, ref):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        gate(bool(np.isfinite(a).all()), f"{name} not finite")
        err, scale = float(np.abs(a - r).max()), float(np.abs(r).max())
        errs[name] = {"max_abs_err": err, "ref_max_abs": scale}
        gate(err <= KERNEL_TOL * scale,
             f"{name}: max abs err {err} > {KERNEL_TOL} * {scale}")
    return {"shape": {"B": b, "S": s, "H": h, "HKV": hkv, "D": d},
            "blocks": [bq, bk], "interpret": rehearse,
            "tpu_custom_calls": n_kernels, "tolerance_rel_to_max": KERNEL_TOL,
            "errors": errs, "smoke_fwd_bwd_seconds": round(flash_s, 5)}


# ----------------------------------------------------------- train_llama

def llama_trainer(cfg, mesh, seq: int):
    """The decoder trainer of the one-chip job: Trainer + sharding_rules +
    chunked CE + Adafactor, default (full) remat."""
    import jax.numpy as jnp
    import optax

    from tpucfn.models.llama import (Llama, chunked_causal_lm_loss,
                                     sharding_rules)
    from tpucfn.train import Trainer

    model = Llama(cfg)
    sample = jnp.zeros((max(2, mesh.size), seq), jnp.int32)

    def init_fn(rng):
        return model.init(rng, sample)["params"], {}

    def loss_fn(params, mstate, batch, rng):
        h = model.apply({"params": params}, batch["tokens"],
                        return_hidden=True)
        loss, acc = chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], batch["tokens"], chunk_size=512)
        return loss, ({"accuracy": acc}, mstate)

    return Trainer(mesh, sharding_rules(cfg), loss_fn, optax.adafactor(1e-3),
                   init_fn)


def llama_steps(trainer, mesh, cfg, batch_size: int, seq: int, steps: int):
    """Fresh seeded state, ``steps`` steps on one fixed seeded batch.
    Returns (losses, step seconds, compiled step text, final state)."""
    import jax
    import numpy as np

    from tpucfn.parallel import shard_batch

    state = trainer.init(jax.random.key(SEED))
    tokens = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (batch_size, seq)).astype(np.int32)
    batch = shard_batch(mesh, {"tokens": tokens})
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))  # value fetch = device sync
        secs.append(time.perf_counter() - t0)
    text = (trainer._jit_step.lower(trainer.abstract_state(), batch)
            .compile().as_text())
    return losses, secs, text, state


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def phase_train_llama(rehearse: bool) -> dict:
    import jax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny() if rehearse else LlamaConfig.llama3_1b()
    # (a) S=2048, where kernels.auto runs this model's D=64 heads dense;
    # (b) the length at which it picks flash for them.  Batch 2 in (a):
    # with dense fp32 (B,32,S,S) scores beside 5.6 GB of fp32 state the
    # README's batch 4 is refused by today's compiler (16.5 of 15.75 GB).
    legs = ({"a": (2, 128), "b": (1, 256)} if rehearse
            else {"a": (2, 2048), "b": (1, 8192)})
    devices = jax.devices()[:1]
    mesh = build_mesh(MeshSpec(), devices)
    on_tpu = devices[0].platform == "tpu"
    facts = {"config": "tiny" if rehearse else "llama3_1b",
             "n_layers": cfg.n_layers, "dim": cfg.dim, "heads":
             [cfg.n_heads, cfg.n_kv_heads], "ffn_dim": cfg.ffn_dim,
             "vocab": cfg.vocab_size, "optimizer": "adafactor"}
    for leg, (bsz, seq) in legs.items():
        trainer = llama_trainer(cfg, mesh, seq)
        losses, secs, text, state = llama_steps(trainer, mesh, cfg, bsz, seq, 4)
        n_params = sum(x.size for x in jax.tree.leaves(state.params))
        del state, trainer
        gc.collect()
        n_kernels = text.count("tpu_custom_call")
        gate(all(math.isfinite(x) for x in losses), f"leg {leg}: {losses}")
        gate(losses[3] < losses[0], f"leg {leg}: loss did not fall {losses}")
        if on_tpu:  # what the dispatch chose, read from the program
            gate((n_kernels > 0) == (leg == "b"),
                 f"leg {leg}: {n_kernels} tpu_custom_call in the step")
        facts[leg] = {
            "batch": bsz, "seq": seq, "params": n_params, "losses": losses,
            "step_seconds": [round(x, 4) for x in secs],
            "tpu_custom_calls": n_kernels,
            "smoke_tokens_per_s": round(2 * bsz * seq / sum(secs[2:]), 1),
            "smoke_peak_bytes_in_use": peak_bytes(devices[0])}
    return facts


# ----------------------------------------------------------------- serve

def phase_serve(rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpucfn.models.generate import generate
    from tpucfn.serve import Server
    from tpucfn.serve.engine import demo_llama_engine
    from tpucfn.serve.scheduler import MIN_PREFILL_BUCKET, prefill_bucket

    # `tpucfn serve --preset llama3-1b` with its defaults, as cmd_serve
    # builds it: one engine, one Server, in this process.
    preset, lo, hi, max_new = (("tiny", 16, 48, 8) if rehearse
                               else ("llama3-1b", 64, 256, 32))
    cfg, engine = demo_llama_engine(preset, seed=SEED, max_batch=8,
                                    cache_len=None, prefill_width=4)
    server = Server(engine, num_blocks=256, block_size=16)

    rs = np.random.RandomState(SEED)
    lens = rs.randint(lo, hi + 1, 8)
    lens[1] = lens[0]  # the two compared prompts: generate compiles once
    prompts = [rs.randint(0, cfg.vocab_size, n).tolist() for n in lens]
    # the last three share a leading system prompt (prefix-cache path)
    for p in prompts[5:]:
        p[:lo] = prompts[4][:lo]

    server.start()
    try:
        t0 = time.perf_counter()
        reqs = [server.submit(p, max_new_tokens=max_new, temperature=0.0)
                for p in prompts]
        outs = [r.result(timeout=900) for r in reqs]
        wall = time.perf_counter() - t0
    finally:
        server.stop()
    gate(all(r.status == "ok" for r in reqs), [r.status for r in reqs])
    gate(all(len(o) == max_new for o in outs), [len(o) for o in outs])
    gate(server.kv.allocator.num_used == 0,
         f"{server.kv.allocator.num_used} KV blocks leaked after the drain")
    snap = server.metrics.snapshot()
    counts = engine.compile_counts()
    hits = int(snap["prefix_hit_requests"])
    # The compile budget: prefill programs bounded by the bucket family
    # (never by the request count), one decode, one copy_prefix once a
    # prefix hit has run.
    family = {prefill_bucket(n, engine.cache_len)
              for n in range(MIN_PREFILL_BUCKET, hi + 1)}
    gate(counts["decode"] == 1 and 1 <= counts["prefill"] <= len(family)
         and counts["copy_prefix"] == (1 if hits else 0), counts)

    # Reference: models.generate on the same parameters.  First token
    # gated (same prefill arithmetic); later ones printed — bf16 near-ties
    # under random weights may part.
    gen = jax.jit(lambda p, t: generate(cfg, p, t, max_new_tokens=max_new,
                                        temperature=0.0))
    agree = []
    for i in (0, 1):
        ref = np.asarray(gen(engine.params,
                             jnp.asarray([prompts[i]], jnp.int32)))[0]
        ref = ref[len(prompts[i]):].tolist()
        gate(ref[0] == outs[i][0],
             f"prompt {i}: first token {outs[i][0]} != generate's {ref[0]}")
        n = next((j for j in range(max_new) if ref[j] != outs[i][j]), max_new)
        agree.append(n)
    return {"preset": preset, "requests": len(reqs), "max_new": max_new,
            "prompt_lens": [len(p) for p in prompts],
            "cache_len": engine.cache_len, "compile_counts": counts,
            "prefix_hit_requests": hits,
            "kv_blocks_used_after": server.kv.allocator.num_used,
            "leading_tokens_agreeing_with_generate": agree,
            "smoke_wall_seconds_with_compiles": round(wall, 2),
            "smoke_peak_bytes_in_use": peak_bytes(jax.devices()[0])}


# --------------------------------------------------------- train_example

def phase_train_example(rehearse: bool) -> dict:
    import importlib.util

    import jax

    from tpucfn.ckpt import CheckpointManager
    from tpucfn.data import native

    run_dir = os.path.join(SCRATCH, "train_example")
    shutil.rmtree(run_dir, ignore_errors=True)
    steps = 4
    argv = ["--run-dir", run_dir, "--steps", str(steps), "--ckpt-every", "2",
            "--log-every", "1", "--seed", str(SEED)]
    argv += (["--network", "resnet18", "--image-size", "32", "--batch-size",
              "8", "--num-examples", "32", "--num-classes", "10"] if rehearse
             else ["--batch-size", "256", "--num-examples", "1024"])

    # README step 3 on one host: the example's own main(), in-process —
    # the program `tpucfn launch` fans out.
    spec = importlib.util.spec_from_file_location(
        "imagenet_resnet50", os.path.join(ROOT, "examples",
                                          "imagenet_resnet50.py"))
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    old_argv, sys.argv = sys.argv, ["imagenet_resnet50.py", *argv]
    try:
        rc = example.main()
    finally:
        sys.argv = old_argv
    gate(rc == 0, f"examples/imagenet_resnet50.py main() returned {rc}")

    with open(os.path.join(run_dir, "logs", "train-host000.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    losses = [r["loss"] for r in rows if "loss" in r]
    gate(len(losses) == steps and all(math.isfinite(x) for x in losses),
         f"losses {losses}")
    with CheckpointManager(os.path.join(run_dir, "ckpt")) as ckpt:
        latest = ckpt.latest_step()
    saved = sorted(int(n) for n in os.listdir(os.path.join(run_dir, "ckpt"))
                   if n.isdigit())
    gate(latest == steps and 2 in saved, f"finalized checkpoints {saved}")
    use_native = native.native_available()
    gate(use_native or rehearse and jax.devices()[0].platform != "tpu",
         f"native tpurecord reader not in use: {native._lib_error}")
    # Gates met: drop the shards and checkpoints (hundreds of MB that the
    # chip tool would otherwise copy with the tree on the next call).
    shutil.rmtree(run_dir, ignore_errors=True)
    ttfs = [r["time_to_first_step"] for r in rows if "time_to_first_step" in r]
    step_s = [r["step_time"] for r in rows if "step_time" in r]
    return {"argv": argv[2:], "rc": rc, "losses": losses,
            "finalized_checkpoints": saved, "native_reader": use_native,
            "native_reader_error": native._lib_error,
            "smoke_time_to_first_step_s": ttfs[0] if ttfs else None,
            "smoke_step_seconds": [round(x, 4) for x in step_s],
            "smoke_peak_bytes_in_use": peak_bytes(jax.devices()[0])}


# ----------------------------------------------------------------- fsdp4

def phase_fsdp4(rehearse: bool) -> dict:
    import jax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny() if rehearse else LlamaConfig.llama3_1b()
    # One sequence a chip under fsdp=4; S=1024 because the one-chip side
    # of the comparison must hold the same global batch (see leg (a)).
    bsz, seq = (4, 128) if rehearse else (4, 1024)
    devices = jax.devices()
    facts = {"config": "tiny" if rehearse else "llama3_1b",
             "global_batch": bsz, "seq": seq}

    mesh4 = build_mesh(MeshSpec(fsdp=4), devices)
    trainer = llama_trainer(cfg, mesh4, seq)
    losses4, secs4, text, state = llama_steps(trainer, mesh4, cfg, bsz, seq, 3)
    per_dev = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(state.params):
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(state.params))
    del state, trainer
    gc.collect()
    share = {i: n / total for i, n in per_dev.items()}
    # Nothing silently landed whole on the first chip: every device holds
    # a quarter of the parameter bytes (replicated norm scales are ~1e-5).
    gate(all(0.24 <= s <= 0.27 for s in share.values()),
         f"parameter share per device {share}")
    collectives = {op: text.count(op) for op in
                   ("all-gather", "reduce-scatter", "all-reduce")}
    gate(collectives["all-gather"] > 0 and
         collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
         f"collectives in the step: {collectives}")
    facts["fsdp4"] = {
        "losses": losses4, "step_seconds": [round(x, 4) for x in secs4],
        "param_bytes_per_device": per_dev, "param_bytes_total": total,
        "collectives_in_step_text": collectives,
        "memory_stats": {d.id: d.memory_stats() for d in devices}}

    mesh1 = build_mesh(MeshSpec(), devices[:1])
    trainer = llama_trainer(cfg, mesh1, seq)
    losses1, secs1, _, state = llama_steps(trainer, mesh1, cfg, bsz, seq, 3)
    del state, trainer
    gc.collect()
    facts["one_chip"] = {"losses": losses1,
                         "step_seconds": [round(x, 4) for x in secs1]}
    diffs = [abs(a - b) for a, b in zip(losses4, losses1)]
    facts["loss_abs_diff"] = diffs
    facts["loss_tolerance"] = FSDP_LOSS_TOL
    gate(all(math.isfinite(x) for x in losses4 + losses1), "loss not finite")
    gate(all(x <= FSDP_LOSS_TOL for x in diffs),
         f"fsdp=4 and one-chip losses differ by {diffs}")
    return facts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fsdp4 phase, on exactly four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX has (tests)")
    args = ap.parse_args()

    os.makedirs(SCRATCH, exist_ok=True)
    # Blocks come from the committed tune table only: the user's own
    # tune file (default under the home directory) is not read.
    os.environ["TPUCFN_FLASH_TUNE_CACHE"] = os.path.join(
        SCRATCH, "no_user_flash_tune.json")
    sys.path.insert(0, ROOT)

    import jax

    from tpucfn.obs import enable_compile_cache

    cache_dir = enable_compile_cache()  # before the first compile
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU — JAX's first device is {device}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    # (a one-chip rehearsal takes whatever virtual devices the tests have)
    if (args.four_chips or not args.rehearse) and len(devices) != want:
        print(f"chip_smoke: needs {want} device(s), JAX has {len(devices)}",
              file=sys.stderr)
        return 2
    print(json.dumps({"phase": "start", "device": device,
                      "rehearsal": args.rehearse, "compile_cache": cache_dir,
                      "jax": jax.__version__}), flush=True)

    phases = Phases()
    if args.four_chips:
        phases.run("fsdp4", phase_fsdp4, args.rehearse)
    else:
        phases.run("kernel", phase_kernel, args.rehearse)
        phases.run("train_llama", phase_train_llama, args.rehearse)
        phases.run("serve", phase_serve, args.rehearse)
        phases.run("train_example", phase_train_example, args.rehearse)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
