#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training images/sec/chip.

This is BASELINE.md's primary metric. The reference repo published no
numbers (BASELINE.json `"published": {}`); the denominator for
``vs_baseline`` is the era-appropriate per-accelerator throughput of the
reference's target fleet — ResNet-50 mixed-precision training on the
p3.16xlarge V100s its README benchmarked on, ~400 images/sec/GPU — so
``vs_baseline`` reads as "times faster per chip than the reference stack's
per-GPU number". The self-contained companion is ``detail.mfu``: measured
model flops (XLA cost analysis of the compiled step) ÷ chip peak bf16.

Prints ONE JSON line:
    {"metric": "...", "value": N, "unit": "images/sec/chip", "vs_baseline": N}

Structure: one process, ``worker()``, on whatever device JAX gives it.
The row names that device (``platform`` / ``device_kind``); a run that
finds no TPU fails unless ``JAX_PLATFORMS=cpu`` asked for the CPU by name,
and any failure exits non-zero.  No row is ever read from a file.

Env knobs: TPUCFN_BENCH_PRESET=tiny|full, TPUCFN_BENCH_BATCH (per-chip),
TPUCFN_BENCH_STEPS / _WARMUP (timed/warm step counts), TPUCFN_BENCH_SEQ
(llama sequence length), TPUCFN_BENCH_REMAT=0 (llama: disable remat),
TPUCFN_BENCH_OPT=adamw|adafactor and TPUCFN_BENCH_CE_CHUNK (llama memory
levers), TPUCFN_BENCH_OVERLAP=0 (skip the loader leg),
TPUCFN_BENCH_LOADER_WORKERS (overlap leg: N>0 decode threads, N<0 spawn
processes), TPUCFN_BENCH_WARM_TTFS=1 (re-compile against the persistent
cache and report warm time-to-first-step), TPUCFN_BENCH_PROFILE=<dir>
(XProf-trace the timed steps).
"""

from __future__ import annotations

import json
import os
import sys
import time


REFERENCE_IMAGES_PER_SEC_PER_ACCEL = 400.0  # V100 ResNet-50 fp16, reference-era

def _peak_tflops(device_kind: str) -> float | None:
    # The peak table lives in tpucfn.obs.goodput so the offline bench
    # and the live train_mfu gauge share one denominator.
    from tpucfn.obs.goodput import device_peak_flops

    peak = device_peak_flops(device_kind)
    return peak / 1e12 if peak else None


# Peak HBM bandwidth GB/s per chip by device_kind substring (public specs).
# Paired with XLA cost analysis "bytes accessed", this turns every bench row
# into a roofline point: mfu ≈ MXU-side utilization, hbm_util ≈ memory-side —
# whichever is near 1.0 names the bound (VERDICT r3 weak #2 asked for exactly
# this evidence for the ~30% MFU plateau).
_PEAK_HBM_GBS = (
    ("v6", 1640.0), ("trillium", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0), ("v5e", 819.0), ("v5litepod", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def _peak_hbm_gbs(device_kind: str) -> float | None:
    kind = device_kind.lower()
    for key, gbs in _PEAK_HBM_GBS:
        if key in kind:
            return gbs
    return None


# --------------------------------------------------------------------------
# Worker: the actual benchmark, on whatever backend this process's
# environment selects.
# --------------------------------------------------------------------------


def _measure_trainer(trainer, state, batch, *, steps, warmup, ledger=None):
    """Shared measurement scaffold: compile step, XLA cost analysis,
    warmup, timed async chain. Returns (state, dict).  ``ledger`` (a
    GoodputLedger or None) gets the compile and timed-step durations so
    the bench row can carry the same bucket shares the live fleet
    reports."""
    import time as _time

    import jax

    t0 = _time.perf_counter()
    state, metrics = trainer.step(state, batch)
    float(metrics["loss"])  # value fetch forces a true device sync
    compile_s = _time.perf_counter() - t0
    if ledger is not None:
        ledger.account("compile", compile_s)

    flops_per_dev_step = None
    bytes_per_dev_step = None
    try:
        from tpucfn.obs.goodput import cost_analysis_value

        cost = (trainer._jit_step.lower(trainer.abstract_state(), batch)
                .compile().cost_analysis())
        flops_per_dev_step = cost_analysis_value(cost, "flops")
        bytes_per_dev_step = cost_analysis_value(cost, "bytes accessed")
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        pass

    for _ in range(warmup):
        state, metrics = trainer.step(state, batch)
    float(metrics["loss"])

    # Timed region: enqueue steps and sync once at the end — the state
    # dependency chain forces serial device execution; one final fetch
    # avoids per-step host round-trips.
    # TPUCFN_BENCH_PROFILE=<dir>: capture an XProf trace of exactly this
    # steady-state range (the §5 profiler row pointed at the MFU gap).
    prof_dir = os.environ.get("TPUCFN_BENCH_PROFILE")
    import contextlib as _ctx

    from tpucfn.obs import profile_steps

    if prof_dir:
        # Fresh capture dir: a retried/previous session's trace must not
        # be counted (or sized) as this run's artifact.
        import shutil as _sh

        _sh.rmtree(prof_dir, ignore_errors=True)
    with (profile_steps(prof_dir) if prof_dir else _ctx.nullcontext()):
        t0 = _time.perf_counter()
        for _ in range(steps):
            state, metrics = trainer.step(state, batch)
        final_loss = float(metrics["loss"])
        mean_step = (_time.perf_counter() - t0) / steps
    if ledger is not None:
        ledger.account("step", mean_step * steps, step=steps)

    device = jax.devices()[0]
    peak = _peak_tflops(device.device_kind)
    peak_hbm = _peak_hbm_gbs(device.device_kind)
    mfu = None
    hbm_util = None
    if flops_per_dev_step and peak and device.platform == "tpu":
        mfu = round(flops_per_dev_step / mean_step / (peak * 1e12), 4)
    if bytes_per_dev_step and peak_hbm and device.platform == "tpu":
        hbm_util = round(bytes_per_dev_step / mean_step / (peak_hbm * 1e9), 4)
    out = {
        "mean_step_s": round(mean_step, 5),
        "compile_s": round(compile_s, 2),
        "final_loss": round(final_loss, 4),
        "flops_per_dev_step_g": (round(flops_per_dev_step / 1e9, 1)
                                 if flops_per_dev_step else None),
        "bytes_per_dev_step_g": (round(bytes_per_dev_step / 1e9, 2)
                                 if bytes_per_dev_step else None),
        "peak_bf16_tflops": peak,
        "peak_hbm_gbs": peak_hbm,
        "mfu": mfu,
        "hbm_util": hbm_util,
        "platform": device.platform,
        "device_kind": device.device_kind,
    }
    if prof_dir and os.path.isdir(prof_dir):
        traces = []
        for root, _dirs, files in os.walk(prof_dir):
            for f in files:
                p = os.path.join(root, f)
                traces.append({"file": os.path.relpath(p, prof_dir),
                               "bytes": os.path.getsize(p)})
        out["trace_files"] = sorted(traces, key=lambda t: -t["bytes"])[:8]
        out["trace_total_bytes"] = sum(t["bytes"] for t in traces)
    return state, out


class _ToFloat:
    """Module-level (picklable) so it can cross into MultiProcessLoader
    spawn workers; a closure cannot."""

    def __call__(self, ex, _rs):
        import numpy as np

        return {"image": ex["image"].astype(np.float32) / 255.0,
                "label": ex["label"]}


def _measure_input_overlap(trainer, state, mesh, *, image_hw, classes,
                           global_batch, steps, prestaged_step_s,
                           ledger=None):
    """VERDICT r2 item 6's third leg: drive the SAME train step from the
    real input pipeline (tpurecord shards → ShardedDataset streaming →
    JPEG decode + crop transform → prefetch_to_mesh) and compare the
    steady-state step time against the pre-staged batch. If prefetch
    overlaps compute, the two match; a gap means training is
    input-bound.

    ISSUE 18 fourth leg: the same steps fed by the disaggregated input
    plane (``served_step_s``) — against a real fleet of input hosts
    when the launcher fanned out ``TPUCFN_INPUT_ADDRS``, or an
    in-process InputService over the same shards otherwise
    (``TPUCFN_BENCH_INPUT_SERVE=0`` skips).  Per-step time spent
    waiting on ``next(it)`` is accounted to the goodput ledger as
    ``data_wait`` so the emitted bucket shares name input-boundness the
    same way the live fleet's goodput report does."""
    import time as _time

    import numpy as np

    from tpucfn.data import write_dataset_shards
    from tpucfn.data.images import center_crop_resize, decode_transform, encode_jpeg
    from tpucfn.data.pipeline import ShardedDataset, prefetch_to_mesh
    from tpucfn.data.transforms import Compose

    import pathlib
    import shutil
    import tempfile

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="tpucfn-bench-overlap-"))
    loader = None
    try:
        rs = np.random.RandomState(0)
        n_examples = max(global_batch * 2, 64)

        def gen():
            for _ in range(n_examples):
                img = rs.randint(0, 255, (image_hw + image_hw // 8,) * 2 + (3,),
                                 ).astype(np.uint8)
                yield {"image": np.frombuffer(encode_jpeg(img), np.uint8),
                       "label": rs.randint(classes, size=()).astype(np.int32)}

        shards = write_dataset_shards(gen(), tmp, num_shards=8)

        transform = Compose([decode_transform(),
                             center_crop_resize(image_hw), _ToFloat()])
        # Mirrors the examples' convention: N>0 decode threads in-process,
        # N<0 spawn |N| worker PROCESSES (MultiProcessLoader — the answer
        # when one decode core cannot feed the chip).
        nw = int(os.environ.get("TPUCFN_BENCH_LOADER_WORKERS", "0"))
        if nw < 0:
            from tpucfn.data import MultiProcessLoader

            loader = MultiProcessLoader(
                shards, num_workers=-nw,
                batch_size_per_process=global_batch, seed=0,
                cache_in_memory=False, process_index=0, process_count=1,
                transform=transform)
            it = prefetch_to_mesh(loader.batches(None), mesh)
        else:
            ds = ShardedDataset(
                shards, batch_size_per_process=global_batch, seed=0,
                cache_in_memory=False, process_index=0, process_count=1,
                transform=transform, num_workers=nw)
            it = prefetch_to_mesh(ds.batches(None), mesh)
        def drive(st, it):
            # Warm compile + drain the prefetch queue's head start
            # (depth=2): timing must start from STEADY state, or the
            # first few steps consume pre-staged batches and understate
            # loader latency.  Host-side wait in next(it) is the
            # data_wait bucket; the residual of the timed region is
            # charged to step (the enqueue chain is async — per-step
            # device time is not observable without breaking the
            # pipeline, and the residual is exactly what the wall
            # decomposition needs).
            st, metrics = trainer.step(st, next(it))
            for _ in range(3):
                st, metrics = trainer.step(st, next(it))
            float(metrics["loss"])
            wait_s = 0.0
            t0 = _time.perf_counter()
            for _ in range(steps):
                tw = _time.perf_counter()
                b = next(it)
                wait_s += _time.perf_counter() - tw
                st, metrics = trainer.step(st, b)
            float(metrics["loss"])
            total = _time.perf_counter() - t0
            if ledger is not None:
                ledger.account("data_wait", wait_s)
                ledger.account("step", max(0.0, total - wait_s))
            # returns the final state too: with donate_state the input
            # buffers are consumed, so the next leg must start from the
            # state this one produced, not re-use a donated one.
            return st, total / steps, wait_s / total if total else 0.0

        state, loader_step_s, loader_wait_share = drive(state, it)

        out = {
            "loader_step_s": round(loader_step_s, 5),
            "prestaged_step_s": round(prestaged_step_s, 5),
            "loader_wait_share": round(loader_wait_share, 4),
            "loader_workers": nw,
            "host_cores": os.cpu_count(),
            # ε = 15% + 2ms: scheduling jitter, not a second input budget
            "input_bound": bool(
                loader_step_s > prestaged_step_s * 1.15 + 0.002),
        }

        # served leg: identical steps through the disaggregated input
        # plane.  TPUCFN_INPUT_ADDRS (launcher fan-out) wins; otherwise
        # an in-process InputService over the SAME shards stands in —
        # the served stream is bit-identical to the local order either
        # way, so served_step_s isolates transport+overlap cost.
        addrs = os.environ.get("TPUCFN_INPUT_ADDRS")
        if addrs or os.environ.get("TPUCFN_BENCH_INPUT_SERVE", "1") != "0":
            svc = None
            stream = None
            try:
                from tpucfn.data.service import (
                    AdaptivePrefetcher, InputService, ServiceBatchStream,
                    service_or_local_batches)

                ds2 = ShardedDataset(
                    shards, batch_size_per_process=global_batch, seed=0,
                    cache_in_memory=False, process_index=0,
                    process_count=1, transform=transform, num_workers=0)
                if addrs:
                    stream = service_or_local_batches(ds2)
                    source = "input-hosts"
                else:
                    sw = int(os.environ.get("TPUCFN_BENCH_SERVE_WORKERS",
                                            str(max(2, (os.cpu_count()
                                                        or 2) // 2))))
                    svc = InputService(
                        shards, num_trainers=1,
                        batch_size_per_process=global_batch, seed=0,
                        transform=transform, num_workers=sw,
                        queue_batches=4, host="127.0.0.1").start()
                    stream = AdaptivePrefetcher(ServiceBatchStream(
                        svc.address, 0, process_count=1,
                        batch_size=global_batch, seed=0))
                    source = "in-process"
                it2 = prefetch_to_mesh(iter(stream), mesh)
                state, served_step_s, served_wait_share = drive(state, it2)
                out["served_step_s"] = round(served_step_s, 5)
                out["served_wait_share"] = round(served_wait_share, 4)
                out["served_source"] = source
            except Exception as e:  # noqa: BLE001 — partial row beats none
                out["served_error"] = repr(e)
            finally:
                for closer in (stream, svc):
                    close = getattr(closer, "close", None)
                    if close is not None:
                        try:
                            close()
                        except Exception:  # noqa: BLE001 — teardown
                            pass
        return out
    except Exception as e:  # noqa: BLE001 — the bench must still emit JSON
        return {"error": repr(e)}
    finally:
        if loader is not None:
            loader.close()
        # The prefetch daemon may hold open fds into tmp; on Linux the
        # unlink is safe (open fds stay readable) and a failed later
        # shard open just ends the producer thread.
        shutil.rmtree(tmp, ignore_errors=True)


def _worker_llama(tiny: bool) -> int:
    """Secondary bench (TPUCFN_BENCH_MODEL=llama): Llama causal-LM
    training tokens/sec/chip + MFU. The reference never trained an LLM,
    so vs_baseline is reported as 0.0 (no denominator exists); MFU is
    the self-contained number."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models.llama import (
        Llama, LlamaConfig, chunked_causal_lm_loss, sharding_rules)
    from tpucfn.parallel import shard_batch
    from tpucfn.train import Trainer

    n_dev = jax.device_count()
    if tiny:
        cfg = LlamaConfig.tiny()
        seq, per_chip_batch, steps, warmup = 128, 4, 6, 2
    else:
        cfg = LlamaConfig.llama3_1b()
        # Batch 2: at S=2048 this model's D=64 heads run dense (no tune
        # row), and with fp32 (B,32,S,S) scores beside 5.6 GB of fp32
        # state today's compiler refuses batch 4 on a 16 GB chip (PR 22).
        seq, per_chip_batch, steps, warmup = 2048, 2, 20, 3
    remat_env = os.environ.get("TPUCFN_BENCH_REMAT")
    if remat_env is not None:
        # Remat trades ~1/3 extra flops for activation memory; "0"/none
        # is pure MFU when the model fits, "dots" keeps MXU outputs and
        # recomputes only elementwise ops (the usual TPU middle ground).
        import dataclasses

        cfg = dataclasses.replace(
            cfg, remat={"0": False, "1": True}.get(remat_env, remat_env))
    per_chip_batch = int(os.environ.get("TPUCFN_BENCH_BATCH", per_chip_batch))
    seq = int(os.environ.get("TPUCFN_BENCH_SEQ", seq))
    steps = int(os.environ.get("TPUCFN_BENCH_STEPS", steps))
    warmup = int(os.environ.get("TPUCFN_BENCH_WARMUP", warmup))
    global_batch = per_chip_batch * n_dev

    # MoE variant (TPUCFN_BENCH_MOE_EXPERTS=N): sized so an 8-expert
    # top-2 stack fits one 16G chip with Adafactor. Only the ragged
    # dispatch is runnable at bench scale — the dense one-hot's (T,E,C)
    # temporaries are hundreds of GB here, which is the point of the
    # ragged design (tests/test_moe.py pins the memory analysis).
    moe_experts = int(os.environ.get("TPUCFN_BENCH_MOE_EXPERTS", "0"))
    if moe_experts:
        import dataclasses as _dc

        from tpucfn.models.moe import MoEConfig

        if not tiny:
            cfg = _dc.replace(cfg, dim=1024, n_layers=8, n_heads=16,
                              n_kv_heads=8, ffn_dim=4096)
        cfg = _dc.replace(cfg, moe=MoEConfig(n_experts=moe_experts, top_k=2))

    mesh = build_mesh(MeshSpec.for_devices(n_dev))
    model = Llama(cfg)
    sample = jnp.zeros((max(2, n_dev), seq), jnp.int32)

    def init_fn(rng):
        return model.init(rng, sample)["params"], {}

    # Chunked CE: never materialize the (B, S, 128k) fp32 logits — the
    # single biggest allocation of the naive step (observed 7.8G at B=8
    # on chip, an OOM by itself).
    ce_chunk = int(os.environ.get("TPUCFN_BENCH_CE_CHUNK", "512"))

    def loss_fn(params, mstate, batch, rng):
        if moe_experts:
            from tpucfn.models.moe import collect_moe_aux

            h, muts = model.apply({"params": params}, batch["tokens"],
                                  return_hidden=True,
                                  mutable=["losses", "metrics"])
            aux = collect_moe_aux(muts)
        else:
            h = model.apply({"params": params}, batch["tokens"],
                            return_hidden=True)
            aux = 0.0
        loss, acc = chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=ce_chunk)
        return loss + aux, ({"accuracy": acc}, mstate)

    # Optimizer state is the other memory wall at 1B on one 16 GB chip:
    # AdamW keeps 8 bytes/param (mu+nu fp32) on top of fp32 params and
    # grads — ~16 GB peak before a single activation. The full preset
    # defaults to factored Adafactor (the T5/PaLM-era TPU answer, ~0
    # second-moment memory); the per-step compute it removes is
    # elementwise noise, so tokens/sec and MFU are unaffected.
    opt_name = os.environ.get("TPUCFN_BENCH_OPT",
                              "adamw" if tiny else "adafactor")
    tx = (optax.adafactor(1e-3) if opt_name == "adafactor"
          else optax.adamw(1e-4))

    trainer = Trainer(mesh, sharding_rules(cfg), loss_fn, tx, init_fn)
    state = trainer.init(jax.random.key(0))
    rs = np.random.RandomState(0)
    batch = shard_batch(mesh, {"tokens": rs.randint(
        0, cfg.vocab_size, (global_batch, seq)).astype(np.int32)})

    state, m = _measure_trainer(trainer, state, batch, steps=steps,
                                warmup=warmup)
    # XLA cost analysis counts the lax.scan layer body ONCE, not
    # x n_layers (observed on chip: 8 TFLOP reported vs ~74 actual), so
    # llama MFU uses the standard analytic 6*N*tokens instead.
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    model_flops = 6.0 * n_params * global_batch * seq
    m["xla_cost_flops_g"] = m.pop("flops_per_dev_step_g")
    m["flops_per_dev_step_g"] = round(model_flops / n_dev / 1e9, 1)
    if m["peak_bf16_tflops"] and m["platform"] == "tpu":
        m["mfu"] = round(model_flops / n_dev / m["mean_step_s"]
                         / (m["peak_bf16_tflops"] * 1e12), 4)
    if moe_experts and m.get("mfu") is not None:
        # Analytic 6*N*tokens over TOTAL params overstates MoE flops
        # (only top_k/E of expert params are active per token); report
        # the honest active-fraction MFU alongside.
        mlp_p = sum(x.size for p, x in jax.tree.flatten_with_path(
            state.params)[0] if "experts" in str(p))
        active = (n_params - mlp_p) + mlp_p * cfg.moe.top_k / moe_experts
        m["mfu_active"] = round(m["mfu"] * active / n_params, 4)
        m["active_param_fraction"] = round(active / n_params, 4)
    toks_chip = global_batch * seq / m["mean_step_s"] / n_dev
    size_tag = "llama3_1b" if not tiny else "tiny_llama"
    if moe_experts:
        size_tag = (f"moe{moe_experts}x_top2" if not tiny
                    else f"tiny_moe{moe_experts}x")
    print(json.dumps({
        "metric": f"{size_tag}_train_tokens_per_sec_per_chip",
        "value": round(toks_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": 0.0,
        "detail": {"devices": n_dev, "global_batch": global_batch,
                   "seq_len": seq, "optimizer": opt_name,
                   "ce_chunk": ce_chunk, "moe_experts": moe_experts, **m},
    }))
    return 0


def _worker_llama_decode(tiny: bool) -> int:
    """Serving-side number (net-new vs the training-only reference):
    KV-cache autoregressive decode tokens/sec/chip for the Llama-1B
    proxy.  Times the jitted end-to-end generate() (prefill + N decode
    steps); the per-token decode rate dominates at N >> prompt."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpucfn.models.generate import generate
    from tpucfn.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny() if tiny else LlamaConfig.llama3_1b()
    prompt_len = 16 if tiny else 128
    max_new = 16 if tiny else 128
    batch = int(os.environ.get("TPUCFN_BENCH_BATCH", 2 if tiny else 8))

    from tpucfn.models.llama import Llama

    rs = np.random.RandomState(0)
    prompt = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, prompt_len)),
                         jnp.int32)
    params = Llama(cfg).init(jax.random.key(0), prompt)["params"]

    gen = jax.jit(lambda p, t: generate(
        cfg, p, t, max_new_tokens=max_new, temperature=0.0))
    t0 = _time.perf_counter()
    out = gen(params, prompt)
    jax.block_until_ready(out)
    compile_s = _time.perf_counter() - t0

    iters = 2 if tiny else 3
    t0 = _time.perf_counter()
    for _ in range(iters):
        out = gen(params, prompt)
    jax.block_until_ready(out)
    elapsed = (_time.perf_counter() - t0) / iters

    dev = jax.devices()[0]
    toks_s = batch * max_new / elapsed
    print(json.dumps({
        "metric": ("llama3_1b_decode_tokens_per_sec_per_chip" if not tiny
                   else "tiny_llama_decode_tokens_per_sec_per_chip"),
        "value": round(toks_s, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": 0.0,
        "detail": {"batch": batch, "prompt_len": prompt_len,
                   "max_new_tokens": max_new, "compile_s": round(compile_s, 2),
                   "gen_s": round(elapsed, 3),
                   "platform": dev.platform, "device_kind": dev.device_kind},
    }))
    return 0


def _worker_bert(tiny: bool) -> int:
    """BASELINE config 3 (BERT-base pretrain, the Horovod->JAX launcher
    path): MLM training tokens/sec/chip + MFU (cost analysis is exact
    here — layers are unrolled, no scan)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models import Bert, BertConfig, mlm_loss
    from tpucfn.parallel import shard_batch, transformer_rules
    from tpucfn.train import Trainer

    n_dev = jax.device_count()
    cfg = BertConfig.tiny() if tiny else BertConfig.base()
    seq = 64 if tiny else 512
    per_chip_batch = int(os.environ.get("TPUCFN_BENCH_BATCH",
                                        4 if tiny else 32))
    steps = int(os.environ.get("TPUCFN_BENCH_STEPS", 6 if tiny else 20))
    warmup = int(os.environ.get("TPUCFN_BENCH_WARMUP", 2 if tiny else 3))
    global_batch = per_chip_batch * n_dev
    mesh = build_mesh(MeshSpec.for_devices(n_dev))
    model = Bert(cfg)
    sample = jnp.zeros((1, seq), jnp.int32)
    MASK_ID = 3

    def init_fn(rng):
        return model.init(rng, sample)["params"], {}

    def loss_fn(params, mstate, batch, rng):
        tokens = batch["tokens"]
        r1, r2, r3 = jax.random.split(rng, 3)
        mask = jax.random.uniform(r1, tokens.shape) < 0.15
        swap = jax.random.uniform(r2, tokens.shape)
        randoms = jax.random.randint(r3, tokens.shape, 0, cfg.vocab_size)
        masked = jnp.where(mask & (swap < 0.8), MASK_ID, tokens)
        masked = jnp.where(mask & (swap >= 0.8) & (swap < 0.9), randoms, masked)
        logits = model.apply({"params": params}, masked, train=True,
                             rngs={"dropout": rng})
        loss, acc = mlm_loss(logits, tokens, mask)
        return loss, ({"accuracy": acc}, mstate)

    trainer = Trainer(mesh, transformer_rules(tensor=False), loss_fn,
                      optax.adamw(1e-4, weight_decay=0.01), init_fn)
    state = trainer.init(jax.random.key(0))
    rs = np.random.RandomState(0)
    batch = shard_batch(mesh, {"tokens": rs.randint(
        0, cfg.vocab_size, (global_batch, seq)).astype(np.int32)})
    state, m = _measure_trainer(trainer, state, batch, steps=steps,
                                warmup=warmup)
    toks_chip = global_batch * seq / m["mean_step_s"] / n_dev
    print(json.dumps({
        "metric": ("bert_base_mlm_tokens_per_sec_per_chip" if not tiny
                   else "tiny_bert_mlm_tokens_per_sec_per_chip"),
        "value": round(toks_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": 0.0,
        "detail": {"devices": n_dev, "global_batch": global_batch,
                   "seq_len": seq, **m},
    }))
    return 0


def _worker_unet(tiny: bool) -> int:
    """BASELINE config 5 (SD-1.5 UNet finetune, the streaming config):
    DDPM epsilon-prediction training latents/sec/chip + MFU (convs are
    unrolled — cost analysis exact)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models.unet import UNet, UNetConfig, ddpm_loss
    from tpucfn.parallel import shard_batch, transformer_rules
    from tpucfn.train import Trainer

    n_dev = jax.device_count()
    cfg = UNetConfig.tiny() if tiny else UNetConfig.sd15()
    hw = 8 if tiny else 64  # 64x64x4 latents = 512px images
    ctx_len = 8 if tiny else 77
    per_chip_batch = int(os.environ.get("TPUCFN_BENCH_BATCH",
                                        4 if tiny else 8))
    steps = int(os.environ.get("TPUCFN_BENCH_STEPS", 6 if tiny else 20))
    warmup = int(os.environ.get("TPUCFN_BENCH_WARMUP", 2 if tiny else 3))
    global_batch = per_chip_batch * n_dev
    mesh = build_mesh(MeshSpec.for_devices(n_dev))
    model = UNet(cfg)

    def init_fn(rng):
        return model.init(
            rng, jnp.zeros((1, hw, hw, cfg.in_channels)),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, ctx_len, cfg.context_dim)),
        )["params"], {}

    def loss_fn(params, mstate, batch, rng):
        return ddpm_loss(model, params, batch, rng), ({}, mstate)

    # Finetune-scale AdamW unless memory-constrained (env override).
    opt_name = os.environ.get("TPUCFN_BENCH_OPT", "adamw")
    tx = (optax.adafactor(1e-5) if opt_name == "adafactor"
          else optax.adamw(1e-5))
    trainer = Trainer(mesh, transformer_rules(tensor=False), loss_fn,
                      tx, init_fn)
    state = trainer.init(jax.random.key(0))
    rs = np.random.RandomState(0)
    batch = shard_batch(mesh, {
        "latents": rs.randn(global_batch, hw, hw, cfg.in_channels
                            ).astype(np.float32),
        "context": rs.randn(global_batch, ctx_len, cfg.context_dim
                            ).astype(np.float32),
    })
    state, m = _measure_trainer(trainer, state, batch, steps=steps,
                                warmup=warmup)
    lat_chip = global_batch / m["mean_step_s"] / n_dev
    print(json.dumps({
        "metric": ("sd15_unet_train_latents_per_sec_per_chip" if not tiny
                   else "tiny_unet_train_latents_per_sec_per_chip"),
        "value": round(lat_chip, 2),
        "unit": "latents/sec/chip",
        "vs_baseline": 0.0,
        "detail": {"devices": n_dev, "global_batch": global_batch,
                   "latent_hw": hw, "optimizer": opt_name, **m},
    }))
    return 0


def worker() -> int:
    import jax

    # A measurement path that finds no chip fails: the CPU is measured
    # only when asked for by name (tests, rehearsals), and the row then
    # names it (platform/device_kind) and carries no MFU or HBM share.
    device = jax.devices()[0]
    if device.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"bench: no TPU (first device is {device.platform} "
              f"{device.device_kind!r}); set JAX_PLATFORMS=cpu to rehearse "
              "on the CPU", file=sys.stderr)
        return 2

    # Persistent XLA compilation cache: the second "create-stack → first
    # step" on the same pod skips recompilation (SURVEY.md §7.4 item 6 —
    # keep the time-to-first-step metric from being compile-dominated).
    from tpucfn.obs import enable_compile_cache

    enable_compile_cache()

    # Fleet artifact plane (ISSUE 13 → 18): when the launcher fanned out
    # TPUCFN_COMPILE_CACHE_ADDRS/_DIR, install the process-default
    # compile-cache client so Trainer's jit goes lower → key →
    # local-store / fleet-fetch / compile+publish.  Unset ⇒ None and the
    # step path is byte-identical (pinned by test_compilecache).
    from tpucfn.compilecache import configure_from_env

    cc_client = configure_from_env()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpucfn.bootstrap import converge
    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models import ResNet, ResNetConfig
    from tpucfn.parallel import dense_rules, shard_batch
    from tpucfn.provision import FakeControlPlane, Provisioner
    from tpucfn.spec import ClusterSpec
    from tpucfn.train import Trainer

    tiny = os.environ.get("TPUCFN_BENCH_PRESET", "full") == "tiny"
    which = os.environ.get("TPUCFN_BENCH_MODEL", "resnet")
    if which == "llama":
        return _worker_llama(tiny)
    if which == "llama-decode":
        return _worker_llama_decode(tiny)
    if which == "bert":
        return _worker_bert(tiny)
    if which == "unet":
        return _worker_unet(tiny)
    n_dev = jax.device_count()

    # Bench-local goodput ledger (ISSUE 18): the row carries the SAME
    # bucket decomposition the live fleet's goodput report uses —
    # compile / compile_cached / compile_fetched / step / data_wait plus
    # the idle residual — so "what fraction of wall is the input plane"
    # reads identically offline and in production.
    import pathlib as _pl
    import shutil as _sh
    import tempfile as _tf

    from tpucfn.obs.goodput import GoodputLedger, fleet_window_observation

    gp_dir = _pl.Path(_tf.mkdtemp(prefix="tpucfn-bench-goodput-"))
    ledger = GoodputLedger(gp_dir, 0, role="bench")

    # --- "create-stack" leg of time-to-first-step (BASELINE metric 2).
    # The control plane here is the in-process fake (this environment has
    # no cloud API); what it measures is the framework's own overhead:
    # provisioning state machine + bootstrap convergence + contract load.
    t_stack0 = time.perf_counter()
    prov = Provisioner(FakeControlPlane(steps_to_provision=1))
    rec = prov.create(ClusterSpec(name="bench", accelerator="cpu-1"))
    converge(rec, "/tmp/tpucfn-bench-run")
    provision_s = time.perf_counter() - t_stack0

    if tiny:
        cfg = ResNetConfig(stage_sizes=(1, 1, 1), num_classes=10, bottleneck=False,
                           width=8, cifar_stem=True, dtype=jnp.float32)
        image_hw, per_chip_batch, classes = 32, 8, 10
        steps, warmup = 8, 2
    else:
        cfg = ResNetConfig.resnet50()
        image_hw, per_chip_batch, classes = 224, 256, 1000
        steps, warmup = 30, 5
    per_chip_batch = int(os.environ.get("TPUCFN_BENCH_BATCH", per_chip_batch))
    steps = int(os.environ.get("TPUCFN_BENCH_STEPS", steps))
    warmup = int(os.environ.get("TPUCFN_BENCH_WARMUP", warmup))

    global_batch = per_chip_batch * n_dev
    mesh = build_mesh(MeshSpec.for_devices(n_dev))
    model = ResNet(cfg)
    sample = jnp.zeros((1, image_hw, image_hw, 3))

    def init_fn(rng):
        v = model.init(rng, sample, train=True)
        return v["params"], {"batch_stats": v["batch_stats"]}

    def loss_fn(params, mstate, batch, rng):
        logits, upd = model.apply(
            {"params": params, **mstate}, batch["image"], train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]
        ).mean()
        return loss, ({}, dict(upd))

    trainer = Trainer(
        mesh, dense_rules(fsdp=False), loss_fn,
        optax.sgd(0.1, momentum=0.9), init_fn,
    )

    t0 = time.perf_counter()
    state = trainer.init(jax.random.key(0))
    jax.block_until_ready(state.params)
    init_s = time.perf_counter() - t0

    rs = np.random.RandomState(0)
    batch = shard_batch(mesh, {
        "image": rs.randn(global_batch, image_hw, image_hw, 3).astype(np.float32),
        "label": rs.randint(0, classes, (global_batch,)).astype(np.int32),
    })

    state, m = _measure_trainer(trainer, state, batch, steps=steps,
                                warmup=warmup, ledger=ledger)
    if os.environ.get("TPUCFN_BENCH_WARM_TTFS", "1") == "1":
        # Warm-start time-to-first-step (BASELINE metric 2; default-on
        # since ISSUE 13 so the trajectory tracks cold AND warm): drop
        # the jit executable cache so the next step re-lowers and
        # re-compiles — against the persistent XLA compile cache
        # populated above. The delta vs compile_s is what a relaunch on
        # the same pod pays; `benches/compile_bench.py` measures the
        # fleet artifact plane's cross-process half of the same story.
        jax.clear_caches()
        # With a clear jit cache, the next step re-enters Trainer.step's
        # _maybe_warm — against the persistent XLA cache AND (when
        # configure_from_env installed a client above) the fleet
        # artifact plane, whose outcome names the goodput bucket.
        trainer._jit_step = None
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, batch)
        float(metrics["loss"])
        warm_s = time.perf_counter() - t0
        outcome = cc_client.last_outcome if cc_client is not None else None
        ledger.account({"fetch": "compile_fetched",
                        "compile": "compile"}.get(outcome, "compile_cached"),
                       warm_s)
        if outcome is not None:
            m["compile_cache_outcome"] = outcome
        m["compile_warm_s"] = round(warm_s, 2)
        m["warm_time_to_first_step_s"] = round(
            provision_s + init_s + warm_s, 2)
        # legacy alias, kept so older trajectory readers keep parsing
        m["time_to_first_step_warm_s"] = m["warm_time_to_first_step_s"]
    if os.environ.get("TPUCFN_BENCH_OVERLAP", "1") == "1":
        m["overlap"] = _measure_input_overlap(
            trainer, state, mesh, image_hw=image_hw, classes=classes,
            global_batch=global_batch, steps=steps,
            prestaged_step_s=m["mean_step_s"], ledger=ledger)
    ledger.close()
    gp = fleet_window_observation(gp_dir)
    _sh.rmtree(gp_dir, ignore_errors=True)
    if gp is not None:
        shares = {k: float(v) for k, v in gp["shares"].items()}
        bad = {k: v for k, v in shares.items() if not 0.0 <= v <= 1.0}
        if bad:
            # rc-gate: a malformed decomposition must fail the worker,
            # not ship a row whose columns cannot be trusted.
            raise RuntimeError(f"goodput shares out of [0, 1]: {bad}")
        m["goodput"] = {
            "wall_s": round(gp["wall_s"], 3),
            "goodput_ratio": round(gp["goodput_ratio"], 4),
            "shares": {k: round(v, 4) for k, v in sorted(shares.items())},
        }
    ips_chip = global_batch / m["mean_step_s"] / n_dev
    print(json.dumps({
        "metric": "resnet50_imagenet_train_images_per_sec_per_chip"
        if not tiny else "tiny_resnet_train_images_per_sec_per_chip",
        "value": round(ips_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(ips_chip / REFERENCE_IMAGES_PER_SEC_PER_ACCEL, 3),
        "detail": {
            "devices": n_dev,
            "global_batch": global_batch,
            "init_s": round(init_s, 2),
            "time_to_first_step_s": round(
                provision_s + init_s + m["compile_s"], 2),
            **m,
        },
    }))
    return 0


def main() -> int:
    return worker()


if __name__ == "__main__":
    sys.exit(main())
