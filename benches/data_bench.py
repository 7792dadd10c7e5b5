#!/usr/bin/env python
"""Input-pipeline throughput bench (VERDICT r2 item 6; SURVEY.md §7.4
item 4 "keeping TPUs fed").

Host-side measurements — meaningful on any machine, no accelerator
involved. Prints one JSON line per phase:

* ``reader``: raw shard scan MB/s, C++ native reader vs the pure-Python
  fallback, over the same tpurecord shards.
* ``decode``: end-to-end ShardedDataset images/sec per host process on
  JPEG-encoded shards (read → CRC → decode_example → JPEG decode →
  center-crop → stack), streaming mode, with the decoded-array path for
  comparison.

Whether training is input-bound on the chip is the benchmark's to say
(``data_wait_share.*`` and ``idle_in_data_wait.*`` in ``BENCHMARK.json``),
with the real ShardedDataset+prefetch loader feeding the real step.

Usage: python benches/data_bench.py [--examples N] [--image-size S]
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def _write_raw_shards(tmp: Path, n: int, image_size: int, num_shards: int):
    """Raw float32 image shards — big payloads, measures IO not decode."""
    from tpucfn.data import synthetic_imagenet, write_dataset_shards

    d = tmp / "raw"
    d.mkdir()
    return write_dataset_shards(
        synthetic_imagenet(n, image_size=image_size, classes=100),
        d, num_shards=num_shards)


def _write_jpeg_shards(tmp: Path, n: int, image_size: int, num_shards: int):
    from tpucfn.data import synthetic_imagenet, write_dataset_shards
    from tpucfn.data.images import encode_jpeg

    def gen():
        for ex in synthetic_imagenet(n, image_size=image_size, classes=100):
            img = (np.clip(ex["image"], 0, 1) * 255).astype(np.uint8)
            yield {"image": np.frombuffer(encode_jpeg(img), np.uint8),
                   "label": ex["label"]}

    d = tmp / "jpeg"
    d.mkdir()
    return write_dataset_shards(gen(), d, num_shards=num_shards)


def bench_reader(shards, label) -> dict:
    from tpucfn.data import native, records

    total_bytes = sum(Path(p).stat().st_size for p in shards)

    def scan(read):
        t0 = time.perf_counter()
        n = sum(len(payload) for p in shards for payload in read(p))
        return n, time.perf_counter() - t0

    # Warm the page cache once so both readers measure the same thing.
    scan(records.read_record_shard)

    _, py_s = scan(records.read_record_shard)
    row = {
        "phase": f"reader_{label}",
        "total_mb": round(total_bytes / 1e6, 1),
        "python_mb_s": round(total_bytes / 1e6 / py_s, 1),
        "native_available": native.native_available(),
    }
    if native.native_available():
        _, nat_s = scan(native.read_record_shard_native)
        row["native_mb_s"] = round(total_bytes / 1e6 / nat_s, 1)
        row["native_speedup"] = round(py_s / nat_s, 2)
    return row


def _write_small_record_shards(tmp: Path, n: int, num_shards: int):
    """Token-sized (~4 KB) records — the shape where per-record overhead
    dominates and the native batch path is supposed to win."""
    from tpucfn.data import write_dataset_shards

    rs = np.random.RandomState(0)

    def gen():
        for _ in range(n):
            yield {"tokens": rs.randint(0, 32000, 1024).astype(np.int32)}

    d = tmp / "small"
    d.mkdir()
    return write_dataset_shards(gen(), d, num_shards=num_shards)


def bench_decode(jpeg_shards, raw_shards, batch: int, image_size: int,
                 workers: int = 0) -> dict:
    from tpucfn.data.images import center_crop_resize, decode_transform
    from tpucfn.data.pipeline import ShardedDataset
    from tpucfn.data.transforms import Compose

    crop = image_size - image_size // 8

    def throughput(shards, transform, num_workers=0):
        ds = ShardedDataset(
            shards, batch_size_per_process=batch, seed=0,
            cache_in_memory=False, process_index=0, process_count=1,
            transform=transform, num_workers=num_workers)
        n = 0
        t0 = time.perf_counter()
        for b in ds.epoch(0):
            n += b["image"].shape[0] if hasattr(b["image"], "shape") else batch
        return n / (time.perf_counter() - t0)

    tf = Compose([decode_transform(), center_crop_resize(crop)])
    jpeg_ips = throughput(jpeg_shards, tf)
    raw_ips = throughput(raw_shards, None)
    out = {
        "phase": "decode",
        "jpeg_decode_crop_images_s": round(jpeg_ips, 1),
        "raw_passthrough_images_s": round(raw_ips, 1),
        "batch": batch,
        "image_size": image_size,
    }
    if workers:
        w_ips = throughput(jpeg_shards, tf, num_workers=workers)
        out[f"jpeg_decode_crop_images_s_w{workers}"] = round(w_ips, 1)
        out["worker_speedup"] = round(w_ips / jpeg_ips, 2)
    return out


class _SleepDecode:
    """Deterministic synthetic 'decode': a per-example sleep.  The
    input-bound shape from the bench record (5.50 s loader vs 0.101 s
    step), scaled down — sleep releases the GIL, so the service's
    thread-pooled decode genuinely parallelizes it."""

    def __init__(self, seconds: float):
        self.seconds = seconds

    def __call__(self, ex, rs):
        time.sleep(self.seconds)
        return ex


def bench_service(tmp: Path, *, batches: int, batch: int, compute_s: float,
                  decode_s: float, workers: int, num_shards: int = 4) -> dict:
    """ISSUE 11 acceptance row: step time on a synthetic INPUT-BOUND
    workload, three ways —

    * ``prestaged_step_s``  every batch already in RAM (the floor:
      pure 'compute'),
    * ``loader_step_s``     the local single-threaded loader (decode
      serializes with compute — the recorded stall, in miniature),
    * ``served_step_s``     fed by an in-process InputService whose
      decode runs ``workers`` wide and OVERLAPS compute through the
      adaptive prefetcher.

    ``ok`` gates the acceptance bound: served within 1.5x of prestaged.
    The first few served steps pay the cold stream (no head start) and
    are excluded from the steady-state mean, exactly like a compile
    warmup step.
    """
    from tpucfn.data import write_dataset_shards
    from tpucfn.data.pipeline import ShardedDataset
    from tpucfn.data.service import (AdaptivePrefetcher, InputService,
                                     ServiceBatchStream)

    rs = np.random.RandomState(0)
    d = tmp / "service"
    d.mkdir()
    n = batches * batch
    shards = write_dataset_shards(
        ({"x": rs.randn(64).astype(np.float32)} for _ in range(n)),
        d, num_shards=num_shards)
    tf = _SleepDecode(decode_s)
    # the steady-state window must keep at least one sample, however
    # small --service-batches is
    warmup = min(3, max(0, batches - 1))

    def steady(waits: list, steps: list) -> tuple[float, float]:
        w, s = waits[warmup:], steps[warmup:]
        step = sum(s) / len(s)
        share = sum(w) / sum(s) if sum(s) else 0.0
        return step, share

    def drive(it) -> tuple[float, float]:
        waits, steps = [], []
        for _ in range(batches):
            t0 = time.perf_counter()
            next(it)
            t_wait = time.perf_counter() - t0
            time.sleep(compute_s)
            waits.append(t_wait)
            steps.append(time.perf_counter() - t0)
        return steady(waits, steps)

    def ds(**kw):
        return ShardedDataset(shards, batch_size_per_process=batch, seed=0,
                              process_index=0, process_count=1,
                              transform=tf, **kw)

    # prestaged floor: decode fully paid before the loop starts
    staged = list(ds().epoch(0))[:batches]
    t0 = time.perf_counter()
    for _ in staged:
        time.sleep(compute_s)
    prestaged_step = (time.perf_counter() - t0) / len(staged)

    loader_step, stall_local = drive(iter(ds().batches(None)))

    svc = InputService(shards, num_trainers=1, batch_size_per_process=batch,
                       seed=0, transform=tf, num_workers=workers,
                       queue_batches=4, host="127.0.0.1").start()
    try:
        served_step, stall_served = drive(AdaptivePrefetcher(
            ServiceBatchStream(svc.address, 0, process_count=1,
                               batch_size=batch, seed=0)))
    finally:
        svc.close()
    return {
        "phase": "data_service",
        "loader_step_s": round(loader_step, 5),
        "served_step_s": round(served_step, 5),
        "prestaged_step_s": round(prestaged_step, 5),
        "stall_share_local": round(stall_local, 4),
        "stall_share_served": round(stall_served, 4),
        "batch": batch,
        "batches": batches,
        "decode_s_per_example": decode_s,
        "compute_s": compute_s,
        "service_workers": workers,
        "ok": served_step <= 1.5 * prestaged_step,
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--examples", type=int, default=256)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--num-shards", type=int, default=8)
    p.add_argument("--workers", type=int, default=8,
                   help="also measure the thread-pool decode path at this "
                        "worker count (0 skips)")
    p.add_argument("--service", action="store_true",
                   help="measure ONLY the disaggregated-input row "
                        "(ISSUE 11): local loader vs service-fed vs "
                        "prestaged step time on a synthetic input-bound "
                        "workload; rc 1 unless served is within 1.5x of "
                        "prestaged")
    p.add_argument("--service-batches", type=int, default=24)
    p.add_argument("--service-batch", type=int, default=16)
    p.add_argument("--service-compute-ms", type=float, default=50.0)
    p.add_argument("--service-decode-ms", type=float, default=4.0,
                   help="synthetic per-example decode cost")
    p.add_argument("--service-workers", type=int, default=8)
    args = p.parse_args()

    tmp = Path(tempfile.mkdtemp(prefix="tpucfn-data-bench-"))
    try:
        if args.service:
            row = bench_service(
                tmp, batches=args.service_batches, batch=args.service_batch,
                compute_s=args.service_compute_ms / 1e3,
                decode_s=args.service_decode_ms / 1e3,
                workers=args.service_workers)
            print(json.dumps(row), flush=True)
            return 0 if row["ok"] else 1
        raw = _write_raw_shards(tmp, args.examples, args.image_size,
                                args.num_shards)
        jpeg = _write_jpeg_shards(tmp, args.examples, args.image_size,
                                  args.num_shards)
        small = _write_small_record_shards(tmp, args.examples * 64,
                                           args.num_shards)
        print(json.dumps(bench_reader(raw, "600kb_records")), flush=True)
        print(json.dumps(bench_reader(small, "4kb_records")), flush=True)
        print(json.dumps(bench_decode(jpeg, raw, args.batch,
                                      args.image_size, args.workers)),
              flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
