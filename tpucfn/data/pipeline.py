"""Host-sharded input pipeline with device prefetch.

The hot-path contract from SURVEY.md §3.2: every step, each worker must
have its next batch ready before the previous step's compute finishes —
on the reference this was MXNet's DataIter threads reading RecordIO; here
it is a background thread that assembles the next global batch onto the
mesh (``make_array_from_process_local_data``) while the current step runs,
keeping the TPU fed from host memory without a host↔device sync bubble
(SURVEY.md §7.4 item 4, the "S3→HBM" path).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import queue
import threading
from concurrent.futures import Future
from concurrent.futures import wait as futures_wait
from pathlib import Path
from typing import Any, Iterator, Sequence

import numpy as np

from tpucfn.data import records

# jax is imported lazily (process-identity defaults, the device-transfer
# leg of prefetch_to_mesh): the disaggregated input plane (ISSUE 11)
# runs these loaders on dedicated INPUT hosts that never touch a
# device — `tpucfn data serve` must not pay (or require) a jax import.


def _jax_process_identity() -> tuple[int, int]:
    import jax

    return jax.process_index(), jax.process_count()


# ---- a large batch is assembled in pieces, on several cores -----------------
# One np.stack of 256 cached 602 KB rows (154 MB) into a fresh array took one
# thread of the chip's host (13 cores) 165 ms against a 99 ms step; 15.5 ms of
# that is the copy and the rest the first touch of freshly mapped pages, and
# both split across threads: 98, 70, 58, 53 and 47 ms in 2, 4, 6, 8 and 12
# pieces, some 5 ms more beside a running train loop (PERF.md, PR 26).  So an
# array of a batch that is large enough is filled in disjoint row ranges from
# a small pool.  How many pieces is read from the array's bytes and the cores
# this process may use; nothing sets it.
# 8 MB of fresh pages cost one thread some 9 ms there.  An array under two
# pieces is one np.stack, as ever; up to 32 MiB glibc mostly hands heap memory
# back already touched and the split buys little (16 MB: 1.7-7.7 ms whole,
# 1.9-3.7 in two), beyond that every output is mapped afresh (34 MB: 37.7 ms
# whole, 17.2 in four).
_ASSEMBLE_PIECE_BYTES = 8 << 20
_ASSEMBLE_MAX_PIECES = 8  # 12 bought 5 ms more, and the loop wants cores too
_core_sharers = 1  # loader processes that share this process's cores
_assembled = threading.local()  # .pieces of the batch this thread made last


class _AssemblePool:
    """The process's one pool for batch assembly, made at the first large
    batch: daemon threads ``tpucfn-assemble-N`` that run a call and hand its
    outcome to a ``Future``.  One per process and not per dataset (an input
    host builds a dataset per stream); its threads write no span
    (``input_load`` has one writer, the thread that asked)."""

    _shared: "_AssemblePool | None" = None
    _lock = threading.Lock()

    @classmethod
    def shared(cls) -> "_AssemblePool":
        with cls._lock:
            if cls._shared is None:
                # the asking thread takes a piece itself
                cls._shared = cls(_ASSEMBLE_MAX_PIECES - 1)
            return cls._shared

    def __init__(self, threads: int):
        self._work: queue.SimpleQueue = queue.SimpleQueue()
        for i in range(threads):
            threading.Thread(target=self._run, daemon=True,
                             name=f"tpucfn-assemble-{i}").start()

    def _run(self) -> None:
        while True:
            fut, fn, args, kwargs = self._work.get()
            try:
                fut.set_result(fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001 — result() raises it again
                fut.set_exception(e)

    def submit(self, fn, *args, **kwargs) -> Future:
        fut: Future = Future()
        self._work.put((fut, fn, args, kwargs))
        return fut


def _pieces_for(nbytes: int, rows: int) -> int:
    """In how many pieces an array of ``nbytes`` and ``rows`` rows is
    assembled: as many as it holds ``_ASSEMBLE_PIECE_BYTES``, bounded by the
    cores this process may use (shared among a ``MultiProcessLoader``'s
    workers) and the cap; 1 is whole, today's statement."""
    by_bytes = nbytes // _ASSEMBLE_PIECE_BYTES
    if by_bytes < 2:  # tokens, labels, small images: nothing else is asked
        return 1
    cores = len(os.sched_getaffinity(0)) // _core_sharers
    return max(1, min(by_bytes, cores, _ASSEMBLE_MAX_PIECES, rows))


def _stack(arrays: list) -> tuple[np.ndarray, int]:
    """``np.stack(arrays)``, byte for byte, and in how many pieces it was
    made: a large one is filled in pieces from the pool, into a fresh output
    (never one that an earlier batch used: a caller, or a transfer still in
    flight, may hold that batch)."""
    first, n = arrays[0], len(arrays)
    pieces = _pieces_for(getattr(first, "nbytes", 0) * n, n)
    # mixed shapes must fail, and mixed dtypes promote, as np.stack has them
    if pieces == 1 or not all(
            isinstance(a, np.ndarray) and a.shape == first.shape
            and a.dtype == first.dtype for a in arrays):
        return np.stack(arrays), 1
    out = np.empty((n,) + first.shape, first.dtype)
    cuts = [n * i // pieces for i in range(pieces + 1)]
    pool = _AssemblePool.shared()
    futs = [pool.submit(np.stack, arrays[a:b], out=out[a:b])
            for a, b in zip(cuts[1:-1], cuts[2:])]
    try:
        np.stack(arrays[:cuts[1]], out=out[:cuts[1]])
    finally:
        futures_wait(futs)  # nobody writes to `out` once this returns
    for f in futs:
        f.result()
    return out, pieces


def _assemble(chosen: list[dict]) -> dict[str, np.ndarray]:
    """A list of examples as one batch; the most pieces any of its arrays
    took is left for :func:`_take_pieces` on this thread."""
    batch, most = {}, 1
    for k in chosen[0]:
        batch[k], pieces = _stack([ex[k] for ex in chosen])
        most = max(most, pieces)
    _assembled.pieces = most
    return batch


def _take_pieces() -> int | None:
    """The pieces of the batch the calling thread's ``ShardedDataset`` made
    last (1 = whole), read once: ``None`` where it made none since."""
    return _assembled.__dict__.pop("pieces", None)


class ShardedDataset:
    """Deterministic, per-process-sharded, shuffled batch iterator over
    tpurecord shards.

    Shard ``i`` is owned by process ``i % num_processes`` — the same
    ownership rule the reference applied to RecordIO parts listed in the
    hostfile order. Shuffling is seeded per epoch so every process draws
    from a common permutation schedule and global batches are reproducible
    run-to-run (the reference's implicit input order was not — SURVEY.md
    §7.4 item 1 calls out exactly this divergence risk).

    An array of a batch that holds two or more ``_ASSEMBLE_PIECE_BYTES``
    (8 MB) is assembled in pieces on the process's one small pool
    (``tpucfn-assemble-N``), as many as its bytes and the cores this
    process may use allow, up to 8; a smaller one by one ``np.stack``.
    The bytes are the same either way, and every batch is a fresh array.
    """

    def __init__(
        self,
        shard_paths: Sequence[str | Path],
        *,
        batch_size_per_process: int,
        shuffle: bool = True,
        seed: int = 0,
        drop_remainder: bool = True,
        process_index: int | None = None,
        process_count: int | None = None,
        transform=None,  # per-example Transform (tpucfn.data.transforms)
        cache_in_memory: bool = True,
        shuffle_buffer: int = 2048,
        num_workers: int = 0,
    ):
        """``cache_in_memory=False`` streams shards instead of
        materializing every decoded example in host RAM — required for
        ImageNet-scale datasets (~140 GB encoded; SURVEY.md §3.2's
        DataIter streamed the same way).  Shuffling then uses shard-order
        shuffling + a ``shuffle_buffer``-sized reservoir, seeded per
        (seed, epoch, process) so batches stay reproducible.

        ``num_workers>0`` applies ``transform`` across that many threads
        per batch (PIL decode and numpy release the GIL) — the measured
        answer to one chip consuming ~2500 img/s while a single-threaded
        decode delivers ~650/s.  Still deterministic: per-example
        augmentation seeds are drawn sequentially from the epoch stream
        and order is preserved, so batches are reproducible for a given
        ``num_workers`` setting (0 keeps the exact legacy draw stream;
        >0 uses the per-example-seed stream regardless of worker
        count)."""
        if not shard_paths:
            raise ValueError("no shard paths given")
        self.all_shards = sorted(str(p) for p in shard_paths)
        if process_index is None or process_count is None:
            pi, pc = _jax_process_identity()
            process_index = pi if process_index is None else process_index
            process_count = pc if process_count is None else process_count
        self.pi = process_index
        self.pc = process_count
        self.local_shards = self.all_shards[self.pi :: self.pc]
        if not self.local_shards:
            raise ValueError(
                f"process {self.pi}/{self.pc} owns no shards out of "
                f"{len(self.all_shards)} — stage more shards than processes"
            )
        self.batch = batch_size_per_process
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.transform = transform
        self.cache_in_memory = cache_in_memory
        self.shuffle_buffer = shuffle_buffer
        self.num_workers = num_workers
        self._pool = None
        self._cache: list[dict[str, np.ndarray]] | None = None
        self._len: int | None = None

    def _executor(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=self.num_workers,
                thread_name_prefix="tpucfn-decode")
        return self._pool

    def _load(self) -> list[dict[str, np.ndarray]]:
        if self._cache is None:
            from tpucfn.data import native

            read = (native.read_record_shard_native if native.native_available()
                    else records.read_record_shard)
            out = []
            for p in self.local_shards:
                out.extend(records.decode_example(b) for b in read(p))
            if not out:
                raise ValueError(f"shards {self.local_shards} contain no examples")
            self._cache = out
        return self._cache

    def _num_examples(self) -> int:
        if self._len is None:
            if self.cache_in_memory:
                self._len = len(self._load())
            else:
                self._len = sum(records.shard_record_count(p)
                                for p in self.local_shards)
        return self._len

    def __len__(self) -> int:
        n = self._num_examples()
        return n // self.batch if self.drop_remainder else -(-n // self.batch)

    def epoch(self, epoch: int) -> Iterator[dict[str, np.ndarray]]:
        """One epoch of host-local batches (dicts of stacked arrays)."""
        # One augmentation stream per (seed, epoch, process): consumed in
        # iteration order, so any batch is reproducible from its epoch.
        aug_rs = np.random.RandomState((self.seed, epoch, self.pi, 7))

        def emit(chosen):
            if self.transform is not None:
                if self.num_workers > 0:
                    # Per-example seeds drawn sequentially from the epoch
                    # stream keep the result independent of thread timing;
                    # executor.map preserves order.
                    seeds = aug_rs.randint(0, 2**31 - 1, size=len(chosen))
                    chosen = list(self._executor().map(
                        lambda ex_s: self.transform(
                            ex_s[0], np.random.RandomState(ex_s[1])),
                        zip(chosen, seeds)))
                else:
                    chosen = [self.transform(ex, aug_rs) for ex in chosen]
            return _assemble(chosen)

        if not self.cache_in_memory:
            yield from self._epoch_streaming(epoch, emit)
            return

        examples = self._load()
        order = np.arange(len(examples))
        if self.shuffle:
            # Epoch-keyed seed, offset by process so local orders differ
            # but are reproducible.
            np.random.RandomState((self.seed, epoch, self.pi)).shuffle(order)

        for start in range(0, len(order) - self.batch + 1, self.batch):
            yield emit([examples[i] for i in order[start:start + self.batch]])
        if not self.drop_remainder and len(order) % self.batch:
            yield emit([examples[i]
                        for i in order[len(order) - len(order) % self.batch:]])

    def _epoch_streaming(self, epoch: int, emit) -> Iterator[dict[str, np.ndarray]]:
        """Constant-memory epoch: shuffled shard order + reservoir
        shuffle over ``shuffle_buffer`` decoded examples (≈ one shard's
        worth) instead of the whole dataset in RAM."""
        from tpucfn.data import native

        read = (native.read_record_shard_native if native.native_available()
                else records.read_record_shard)
        rs = np.random.RandomState((self.seed, epoch, self.pi))
        shard_order = list(self.local_shards)
        if self.shuffle:
            rs.shuffle(shard_order)

        def examples():
            for p in shard_order:
                for payload in read(p):
                    yield records.decode_example(payload)

        buf: list = []
        pending: list = []

        def drain_into_batches(ex_iter):
            for ex in ex_iter:
                pending.append(ex)
                if len(pending) == self.batch:
                    out = list(pending)
                    pending.clear()
                    yield emit(out)

        def sampled():
            for ex in examples():
                if not self.shuffle:
                    yield ex
                elif len(buf) < self.shuffle_buffer:
                    buf.append(ex)
                else:
                    j = rs.randint(len(buf))
                    out, buf[j] = buf[j], ex
                    yield out
            if self.shuffle:
                rs.shuffle(buf)
            while buf:
                yield buf.pop()

        yield from drain_into_batches(sampled())
        if not self.drop_remainder and pending:
            yield emit(list(pending))

    def batches(self, num_epochs: int | None = None) -> Iterator[dict[str, np.ndarray]]:
        e = 0
        while num_epochs is None or e < num_epochs:
            yield from self.epoch(e)
            e += 1


def _mp_worker_main(out_q, shard_paths, ds_kwargs, worker_index,
                    num_workers, num_epochs):
    """MultiProcessLoader worker entry point (module-level so spawn can
    pickle it by reference).  Owns shard_paths[worker_index::num_workers]
    via ShardedDataset's process-sharding logic; streams
    ("batch", dict) items, an ("end", epoch) marker per epoch, and a
    final ("done", None) — or ("error", traceback)."""
    global _core_sharers
    _core_sharers = num_workers  # this host's cores are the workers' together
    try:
        ds = ShardedDataset(shard_paths, process_index=worker_index,
                            process_count=num_workers, **ds_kwargs)
        e = 0
        while num_epochs is None or e < num_epochs:
            for batch in ds.epoch(e):
                out_q.put(("batch", batch))
            out_q.put(("end", e))
            e += 1
        out_q.put(("done", None))
    except Exception:  # noqa: BLE001 — surface the traceback to the parent
        import traceback

        out_q.put(("error", traceback.format_exc()))


class MultiProcessLoader:
    """Decode across worker PROCESSES — the answer when one Python
    process cannot feed the chips (measured: a single PIL decode core
    delivers ~550 img/s against a v5e consuming 2524; threads don't
    help, the decode path is GIL/core-bound).  The process analogue of
    the reference's MXNet DataIter decode threads (SURVEY.md §3.2), in
    the shape of a PyTorch DataLoader:

    * this host's shards are sharded again across ``num_workers`` spawn
      processes (worker w owns ``local_shards[w::W]`` with its own
      deterministic shuffle/augmentation stream);
    * each worker streams finished host batches through a bounded queue
      (so memory is ``num_workers * prefetch`` batches);
    * the parent interleaves workers round-robin in a fixed order, so
      the global batch sequence is deterministic for a given
      (seed, num_workers) — like torch, the sequence differs between
      worker counts, never between runs.

    Workers never touch jax devices (pure numpy/PIL), so spawn is safe
    next to an initialized TPU client.  User scripts need the standard
    ``if __name__ == "__main__"`` guard (spawn re-imports __main__).
    Pair with :func:`prefetch_to_mesh` for the host→device overlap leg.
    """

    def __init__(
        self,
        shard_paths: Sequence[str | Path],
        *,
        num_workers: int,
        batch_size_per_process: int,
        seed: int = 0,
        prefetch: int = 4,
        process_index: int | None = None,
        process_count: int | None = None,
        **ds_kwargs,
    ):
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        if process_index is None or process_count is None:
            jpi, jpc = _jax_process_identity()
            process_index = jpi if process_index is None else process_index
            process_count = jpc if process_count is None else process_count
        pi, pc = process_index, process_count
        local = sorted(str(p) for p in shard_paths)[pi::pc]
        if len(local) < num_workers:
            raise ValueError(
                f"process {pi} owns {len(local)} shards < num_workers="
                f"{num_workers} — stage more shards or fewer workers")
        self.local_shards = local
        self.num_workers = num_workers
        self.prefetch = prefetch
        self._len: int | None = None
        # Offset the seed per host process so worker w here and worker w
        # on another host draw different augmentation streams.
        self.ds_kwargs = dict(ds_kwargs, seed=seed + 100003 * pi,
                              batch_size_per_process=batch_size_per_process)
        self._procs: list = []
        self._queues: list = []

    def _start(self, num_epochs):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self.close()
        self._procs, self._queues = [], []
        for w in range(self.num_workers):
            q = ctx.Queue(maxsize=self.prefetch)
            p = ctx.Process(
                target=_mp_worker_main,
                args=(q, self.local_shards, self.ds_kwargs, w,
                      self.num_workers, num_epochs),
                daemon=True, name=f"tpucfn-loader-{w}")
            p.start()
            self._procs.append(p)
            self._queues.append(q)

    def close(self) -> None:
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join(timeout=5)
        self._procs, self._queues = [], []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __len__(self) -> int:
        """Host batches per epoch: the sum of each worker's per-epoch
        batch count (each worker rounds its own remainder, exactly as
        its in-worker ShardedDataset will). Lets epoch-driven training
        loops compute total steps without consuming the stream
        (ADVICE r3: ``len(ds) * num_epochs`` crashed here)."""
        if self._len is None:
            self._len = sum(
                len(ShardedDataset(self.local_shards, process_index=w,
                                   process_count=self.num_workers,
                                   **self.ds_kwargs))
                for w in range(self.num_workers))
        return self._len

    def _get(self, w: int, timeout_s: float = 10.0):
        """Queue read that notices a dead worker: a spawn process killed
        without posting (OOM SIGKILL) would otherwise block the parent
        forever on Queue.get (ADVICE r3).  A ``close()`` that raced the
        read (another thread shutting the loader down mid-iteration —
        the input service's stream teardown path) surfaces as a clean
        RuntimeError instead of an IndexError on the torn queue list."""
        while True:
            if w >= len(self._queues):
                raise RuntimeError(
                    f"loader closed while reading worker {w} — "
                    "close() raced an in-flight iteration")
            try:
                return self._queues[w].get(timeout=timeout_s)
            except queue.Empty:
                if w >= len(self._procs):
                    raise RuntimeError(
                        f"loader closed while reading worker {w} — "
                        "close() raced an in-flight iteration") from None
                p = self._procs[w]
                if not p.is_alive():
                    raise RuntimeError(
                        f"loader worker {w} died (exitcode {p.exitcode}) "
                        "without posting a batch or an error — likely "
                        "killed by the OS (OOM?)") from None

    def batches(self, num_epochs: int | None = None
                ) -> Iterator[dict[str, np.ndarray]]:
        """Round-robin-merged batch stream across workers; epochs stay in
        lockstep (a worker that finished epoch e is skipped until every
        worker has)."""
        self._start(num_epochs)
        w_count = self.num_workers
        done = [False] * w_count
        epoch_ended = [False] * w_count
        try:
            while not all(done):
                for w in range(w_count):
                    if done[w] or epoch_ended[w]:
                        continue
                    tag, payload = self._get(w)
                    if tag == "batch":
                        yield payload
                    elif tag == "end":
                        epoch_ended[w] = True
                    elif tag == "done":
                        done[w] = True
                    else:
                        raise RuntimeError(
                            f"loader worker {w} failed:\n{payload}")
                if all(e or d for e, d in zip(epoch_ended, done)):
                    epoch_ended = [False] * w_count
        finally:
            self.close()


def prefetch_to_mesh(
    it: Iterator[dict[str, np.ndarray]],
    mesh,
    *,
    extra_axes: tuple[str | None, ...] = (),
    depth: int = 2,
    tracer=None,
    first_step: int = 1,
) -> Iterator[Any]:
    """Wrap a host-batch iterator so device transfer overlaps compute.

    A daemon thread stays ``depth`` global batches ahead; the consumer
    always finds its next batch already resident on the mesh.  Closing the
    generator (or dropping it) ends the thread after the batch it is making
    and frees the batches it had placed ahead.

    With a ``tracer`` (:class:`tpucfn.obs.trace.Tracer`) the thread
    writes two spans a batch, ``trace_id`` the step the batch feeds,
    counted from ``first_step``: ``input_load`` around the pull from
    ``it`` (read, transform, stack) and ``input_place`` around the
    placement on the mesh (the host's part of it: the transfer goes on
    after the call has returned), both with the batch's ``bytes``.
    ``input_load`` says in how many ``pieces`` a local
    :class:`ShardedDataset` assembled the batch (1: whole, the batch is
    small or the process has one core; absent where ``it`` is no local
    dataset, as over the input plane); ``input_place`` says how many
    batches were ``queued`` when this one was ready (0: the loop is
    starved; ``depth``: the loader is ahead).

    ``TPUCFN_INPUT_DEVICE_SHARDED=1`` opts into the device-layout
    placement (ISSUE 18 satellite): served rows go to their devices as
    numpy views, skipping the trainer-side staging copy.  Default off —
    the plain path is byte-identical to before the flag existed.
    """
    import jax

    from tpucfn.obs.trace import Tracer
    from tpucfn.parallel.sharding import (
        shard_batch,
        shard_batch_device_layout,
    )

    place = (shard_batch_device_layout
             if os.environ.get("TPUCFN_INPUT_DEVICE_SHARDED") == "1"
             else shard_batch)
    q: queue.Queue = queue.Queue(maxsize=depth)
    _END = object()
    stopped = threading.Event()  # the consumer has gone
    it = iter(it)
    if tracer is None:
        tracer = Tracer(None)  # times, writes nothing

    def producer():
        try:
            for step in itertools.count(first_step):
                with tracer.span("input_load", trace_id=step) as s:
                    host_batch = next(it, _END)
                    if host_batch is _END:
                        s["end_of_stream"] = True
                        break
                    nbytes = sum(x.nbytes for x in
                                 jax.tree_util.tree_leaves(host_batch))
                    s["bytes"] = nbytes
                    pieces = _take_pieces()
                    if pieces is not None:
                        s["pieces"] = pieces
                with tracer.span("input_place", trace_id=step) as s:
                    placed = place(mesh, host_batch, extra_axes)
                    s["bytes"] = nbytes
                    s["queued"] = q.qsize()
                q.put(placed)
                if stopped.is_set():
                    return
        except Exception as e:  # surface pipeline errors to the consumer
            q.put(e)
            return
        q.put(_END)

    t = threading.Thread(target=producer, daemon=True, name="tpucfn-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, Exception):
                raise item
            yield item
    finally:
        # a consumer that stops early leaves the thread blocked in ``q.put``
        # with ``depth`` + 1 batches held on the devices: make room, so that
        # it reads ``stopped`` after the put, and wait for the batch in hand
        stopped.set()
        with contextlib.suppress(queue.Empty):
            while True:
                q.get_nowait()
        t.join(timeout=5.0)


# The disaggregated-input client (ISSUE 11) is part of the pipeline's
# public surface: trainers swap `ds.batches(...)` for
# `service_or_local_batches(ds, ...)` and everything downstream
# (prefetch_to_mesh included) is unchanged.
from tpucfn.data.service import (  # noqa: E402,F401
    AdaptivePrefetcher,
    PrefetchController,
    ResilientBatchStream,
    ServiceBatchStream,
    service_or_local_batches,
)
