"""Process-based shared-memory input pipeline.

The thread-based `BatchLoader` (pipeline.py) is GIL-bound for its numpy
stages: measured r5 (artifacts/r05/calibration/host_loader_bench.json) it
delivers ~49 img/s per host core on the full decode+augment+encode+
normalize path vs a chip consuming 435 img/s at the flagship config — the
FireCaffe failure mode (PAPERS.md): accelerator scaling dies when the data
path can't keep up. `ProcessBatchLoader` removes the GIL from the
steady-state path:

* a **spawn-context worker pool** (fork is unsafe with a live PJRT/XLA
  runtime in the parent) where each worker decodes, augments, encodes and
  normalizes one whole batch;
* **zero-copy shared-memory handoff**: each batch is built directly
  inside its own POSIX shared-memory segment — the worker passes
  `collate` an allocator that carves the output arrays out of the segment
  (no worker-side pack copy), and the parent maps the segment read-only
  and yields numpy views (no parent-side unpack copy; on the measured
  1-core box that copy alone cost ~24% of a 512^2 batch in page-faulted
  memcpy). Only a ~100-byte metadata record and the per-image VOC dicts
  cross the result queue. The parent unlinks the segment the moment it is
  mapped — the pages live exactly as long as the yielded arrays do (mmap
  refcount) and the name can never leak;
* **bit-identical batches**: both loaders reseed the augmentor's RNG per
  batch from `(seed, epoch, batch_index)` (`seed_augmentor_for_batch`,
  pipeline.py), so for a fixed (seed, epoch) the process loader yields
  exactly the thread loader's bytes — property-tested
  (tests/test_shm_pool.py) — and the in-process **fallback** after a
  worker death continues the run bit-identically;
* **failure containment**: workers heartbeat a shared timestamp; the
  parent reaps dead workers (a killed/OOMed worker — Python exceptions
  propagate like the thread loader's) and falls back to the thread path.
  `worker_status()` feeds the train loop's HangWatchdog so a stalled
  input pipeline is diagnosable. Segment names are parent-chosen, so
  even segments a killed worker was mid-write in are swept deterministically.

Leak hygiene (the `resource_tracker` contract): the worker's
`SharedMemory(create=True)` registers the name with the shared tracker;
the parent's unlink (`_unlink_segment`) removes the file AND unregisters.
Clean shutdown, consumer abandonment and SIGKILLed workers all leave
/dev/shm empty and produce no tracker warnings (tested in a fresh
interpreter, tests/test_shm_pool.py).

Device-side overlap (the other half of this PR) lives in
`pipeline.DevicePrefetcher`: it stages the next batch's sharded
`jax.device_put` while the current step executes.

No reference analogue: the reference delegates all of this to
`torch.utils.data.DataLoader(num_workers=N)` (ref train.py:39); this is
the explicit TPU-first equivalent with static shapes and shared-memory
transport. Linux-only (POSIX shm via /dev/shm); on other platforms the
loader falls back to the thread path at pool start.
"""

from __future__ import annotations

import os
import queue as queue_mod
import time
import traceback
import uuid
from multiprocessing import get_context
from multiprocessing.shared_memory import SharedMemory
from typing import Iterator, List, Sequence, Tuple

import numpy as np

from .pipeline import Batch, BatchLoader, collate, seed_augmentor_for_batch

_ALIGN = 64      # field alignment inside a segment
_SHM_DIR = "/dev/shm"


def _aligned(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _max_canvas(augmentor, dataset) -> int:
    """Worst-case square canvas size the augmentor can emit.

    TrainAugmentor exposes `max_size` (the multiscale grid's upper bound),
    TestAugmentor `imsize`; a foreign augmentor is probed on sample 0
    (probe RNG state is irrelevant: batches reseed per (seed, epoch,
    index))."""
    for attr in ("max_size", "imsize"):
        v = getattr(augmentor, attr, None)
        if v:
            return int(v)
    img, bx, lb, _ = dataset[0]
    (img,), _, _ = augmentor([img], [bx], [lb])
    return int(max(img.shape[:2]))


def _segment_capacity(batch_size: int, canvas: int, num_cls: int,
                      scale_factor: int, max_boxes: int, raw: bool) -> int:
    """Bytes one segment must hold for the worst-case batch. Segments are
    ftruncate'd to this size but pages are only materialized on write, so
    over-sizing costs address space, not memory."""
    b, t = batch_size, canvas
    m = -(-t // scale_factor)
    total = 0
    if raw:
        total += _aligned(b * t * t * 3)           # uint8 canvases
        # heatmap/offset/wh/mask are (B, 0, 0, 0) f32 — zero bytes
    else:
        total += _aligned(b * t * t * 3 * 4)       # f32 normalized images
        total += _aligned(b * m * m * num_cls * 4)  # heatmap
        total += 2 * _aligned(b * m * m * 2 * 4)    # offset, wh
        total += _aligned(b * m * m * 1 * 4)        # mask
    total += _aligned(b * max_boxes * 4 * 4)        # boxes f32
    total += _aligned(b * max_boxes * 4)            # labels i32
    total += _aligned(b * max_boxes)                # valid bool
    return total + 4096                             # alignment slack


class _SegmentArena:
    """Worker-side allocator over one batch's shared-memory segment: hands
    `collate` zero-initialized array views (fresh shm pages are
    kernel-zeroed) and records the (field, shape, dtype, offset) metadata
    the parent needs to map them back."""

    def __init__(self, name: str, capacity: int):
        self.shm = SharedMemory(create=True, name=name, size=capacity)
        self.offset = 0
        self.meta: List[Tuple] = []

    def alloc(self, field: str, shape, dtype) -> np.ndarray:
        dtype = np.dtype(dtype)
        count = int(np.prod(shape, dtype=np.int64))
        nbytes = count * dtype.itemsize
        if self.offset + nbytes > self.shm.size:
            raise ValueError(
                "batch (%d bytes at field %r) exceeds the shared-memory "
                "segment capacity %d: the augmentor produced a larger "
                "canvas than the sizing probe predicted; give the "
                "augmentor a max_size/imsize attribute or lower the batch "
                "size" % (self.offset + nbytes, field, self.shm.size))
        arr = np.frombuffer(self.shm.buf, dtype, count=count,
                            offset=self.offset).reshape(shape)
        self.meta.append((field, tuple(shape), dtype.str, self.offset))
        self.offset = _aligned(self.offset + nbytes)
        return arr

    def close(self) -> None:
        """Drop the worker's mapping (file + registration persist; the
        parent owns unlink). Safe only after every view died."""
        try:
            self.shm.close()
        except BufferError:  # a stray view survives: OS reclaims at exit
            pass


def _unlink_segment(name: str) -> None:
    """Parent-side destroy: remove the file and the resource_tracker
    registration the creating worker left (tracker names carry a leading
    slash). Idempotent — a worker that failed mid-batch unlinks its own
    segment, and this sweep must tolerate that."""
    try:
        os.unlink(os.path.join(_SHM_DIR, name))
    except FileNotFoundError:
        return
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister("/" + name, "shared_memory")
    except Exception:  # noqa: BLE001 — accounting only; file is gone
        pass


def _map_batch(meta: Sequence[Tuple], name: str, infos: List[dict]) -> Batch:
    """Map a completed segment read-only and build the Batch as zero-copy
    numpy views. The mmap lives exactly as long as the views (numpy holds
    the buffer), so the caller can unlink the name immediately."""
    import mmap
    with open(os.path.join(_SHM_DIR, name), "rb") as f:
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    fields = {}
    for fname, shape, dtype_str, offset in meta:
        count = int(np.prod(shape, dtype=np.int64))
        fields[fname] = np.frombuffer(mm, np.dtype(dtype_str), count=count,
                                      offset=offset).reshape(shape)
    return Batch(infos=infos, **fields)


def _worker_main(worker_id: int, task_q, result_q, dataset, augmentor,
                 collate_kw, seed: int, heartbeat, capacity: int) -> None:
    """Worker loop: pull (batch_idx, epoch, segment_name, indices) tasks,
    build the batch IN the named segment, send the mapping metadata. Runs
    in a fresh spawned interpreter."""
    try:
        # Pin the worker to CPU before anything can touch a backend: the
        # parent holds the chip (one process per chip), and a child that
        # reached for it would fail or hang. Workers do numpy-only work
        # and never need a device.
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — jax absent/odd builds must not kill I/O
        pass
    while True:
        task = task_q.get()
        if task is None:
            break
        batch_idx, epoch, seg_name, indices = task
        heartbeat.value = time.monotonic()
        arena = None
        batch = None
        try:
            samples = [dataset[int(i)] for i in indices]
            seed_augmentor_for_batch(augmentor, seed, epoch, batch_idx)
            arena = _SegmentArena(seg_name, capacity)
            batch = collate(samples, augmentor, alloc=arena.alloc,
                            **collate_kw)
            result_q.put(("ok", batch_idx, seg_name, arena.meta,
                          batch.infos))
        except BaseException:  # noqa: BLE001 — surfaced to the parent
            result_q.put(("err", batch_idx, seg_name,
                          traceback.format_exc(), None))
            if arena is not None:  # creator-side destroy of the dead batch
                batch = None
                arena.close()
                try:
                    SharedMemory(name=seg_name).unlink()
                except Exception:  # noqa: BLE001
                    pass
                arena = None
        finally:
            batch = None        # drop the views BEFORE releasing the map
            if arena is not None:
                arena.close()
        heartbeat.value = time.monotonic()


class ProcessBatchLoader(BatchLoader):
    """`BatchLoader` with a multi-process shared-memory producer.

    Same constructor, same sharding/shuffle/epoch semantics, bit-identical
    batches (shared `epoch_indices` + per-batch augmentor reseed).

    **Per-host sharding contract (ISSUE 11):** in a multi-process
    data-parallel run each host constructs its loader with its own
    `(rank, world_size)` (train() does) and this pool dispatches ONLY the
    `indices[rank::world_size]` shard to its workers — no sample is
    decoded twice across the fleet, and the union of all hosts' shards
    covers the (seed, epoch)-keyed permutation exactly (wrap-padded so
    every host issues the same number of collectives per epoch — the
    DistributedSampler contract, ref train.py:54). The `quarantine`
    poison-batch guard below applies per host to its own shard
    (rank-disjointness + quarantine-under-sharding are pinned by
    tests/test_shm_pool.py). The
    worker pool starts lazily at first iteration and persists across
    epochs; `close()` (or garbage collection) tears it down. Yielded
    batches hold READ-ONLY arrays backed by their own (already-unlinked)
    shared-memory segment — each batch's memory frees when its arrays die,
    and no buffer is ever reused, so asynchronously-dispatched device
    transfers can never read recycled data.

    Failure semantics:
      * a Python exception in a worker propagates to the consumer, exactly
        like the thread loader;
      * a DEAD worker (killed, OOMed, segfaulted) is reaped: the pool is
        terminated and the remainder of the run is produced in-process by
        the thread path — same bytes, lower throughput, loud warning;
      * `quarantine=True` (ISSUE 9; armed by train's --sentinel): a
        produced batch carrying non-finite float values (a poisoned input
        shard, a decode blowup) is QUARANTINED — counted, reported as a
        `recover:quarantine` flight-recorder event and dropped before it
        can reach the train step — instead of burning a step (or, without
        the in-jit sentinel, silently poisoning the run). Off by default:
        the finite scan costs a pass over the batch's float bytes.
    """

    def __init__(self, *args, quarantine: bool = False, **kw):
        super().__init__(*args, **kw)
        self.quarantine = bool(quarantine)
        self.quarantined = 0
        # one tracer for the loader's recover:quarantine events (honors
        # $OBS_SPAN_LOG; disabled tracers cost nothing)
        from ..obs.spans import maybe_tracer
        self._obs = maybe_tracer() if quarantine else None
        self._ctx = get_context("spawn")
        self._procs: List = []
        self._heartbeats: List = []
        self._task_q = None
        self._result_q = None
        self._capacity = 0
        self._prefix = "helmet_shm_%d_%s" % (os.getpid(),
                                             uuid.uuid4().hex[:8])
        self._iter_seq = 0     # unique segment names across iterations
        self._fell_back = False
        self._finalizer = None

    # -- pool lifecycle ----------------------------------------------------

    def _start_pool(self) -> None:
        import weakref
        if not os.path.isdir(_SHM_DIR):
            raise OSError("%s not available (POSIX shm is Linux-only)"
                          % _SHM_DIR)
        canvas = _max_canvas(self.augmentor, self.dataset)
        self._capacity = _segment_capacity(
            self.batch_size, canvas, self.kw["num_cls"],
            self.kw["scale_factor"], self.kw["max_boxes"], self.kw["raw"])
        self._task_q = self._ctx.Queue()
        self._result_q = self._ctx.Queue()
        for w in range(self.num_workers):
            hb = self._ctx.Value("d", 0.0, lock=False)
            p = self._ctx.Process(
                target=_worker_main,
                args=(w, self._task_q, self._result_q, self.dataset,
                      self.augmentor, self.kw, self.seed, hb,
                      self._capacity),
                daemon=True)
            p.start()
            self._procs.append(p)
            self._heartbeats.append(hb)
        # gc safety net: terminate workers + sweep any segment carrying
        # this loader's prefix if the loader is dropped without close()
        self._finalizer = weakref.finalize(
            self, _cleanup, list(self._procs), self._prefix,
            self._task_q, self._result_q)

    def _stop_pool(self) -> None:
        if self._finalizer is not None:
            self._finalizer.detach()
            self._finalizer = None
        _cleanup(self._procs, self._prefix, self._task_q, self._result_q)
        self._procs = []
        self._heartbeats = []
        self._task_q = None
        self._result_q = None

    def close(self) -> None:
        """Terminate workers and sweep any in-flight segments. Already-
        yielded batches stay valid (their segments are unlinked views —
        the memory outlives the name)."""
        self._stop_pool()

    def __del__(self):  # pragma: no cover - finalizer covers the real path
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass

    # -- observability -----------------------------------------------------

    def worker_status(self) -> str:
        """One-line worker health summary for the HangWatchdog warning."""
        if not self._procs:
            return "loader: process pool not started"
        now = time.monotonic()
        parts = []
        for i, (p, hb) in enumerate(zip(self._procs, self._heartbeats)):
            age = ("%.0fs" % (now - hb.value)) if hb.value else "never"
            parts.append("w%d=%s/hb:%s" % (
                i, "up" if p.is_alive() else "DEAD", age))
        if self._fell_back:
            parts.append("FELL-BACK-TO-THREAD")
        if self.quarantined:
            parts.append("quarantined:%d" % self.quarantined)
        return "loader workers: " + " ".join(parts)

    # -- poison-batch quarantine (ISSUE 9) ---------------------------------

    def _quarantine_batch(self, batch: Batch, batch_idx: int,
                          epoch: int) -> bool:
        """True if `batch` is poisoned (non-finite floats) and was
        quarantined. The scan covers every float field the step consumes;
        uint8 canvases (raw mode) have nothing to scan — their GT boxes
        still do."""
        if not self.quarantine:
            return False
        for name in ("image", "heatmap", "offset", "wh", "boxes"):
            arr = getattr(batch, name, None)
            if not (isinstance(arr, np.ndarray) and arr.dtype.kind == "f"
                    and arr.size):
                continue
            if not np.isfinite(arr).all():
                self.quarantined += 1
                print("process loader: QUARANTINED poisoned batch %d "
                      "(epoch %d): non-finite values in %r (total "
                      "quarantined: %d)" % (batch_idx, epoch, name,
                                            self.quarantined), flush=True)
                if self._obs is not None:
                    self._obs.event("recover:quarantine", batch=batch_idx,
                                    epoch=epoch, field=name)
                return True
        return False

    # -- iteration ---------------------------------------------------------

    def _fallback_batches(self, chunks, start_idx: int,
                          epoch: int) -> Iterator[Batch]:
        """Produce batches [start_idx:] in-process (thread path). Same
        bytes as the workers would have produced: content depends only on
        (seed, epoch, batch_idx)."""
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(self.num_workers) as pool:
            for bi in range(start_idx, len(chunks)):
                batch = self._make_batch(pool, chunks[bi], epoch=epoch,
                                         batch_idx=bi)
                if self._quarantine_batch(batch, bi, epoch):
                    continue
                yield batch

    def __iter__(self) -> Iterator[Batch]:
        epoch = self.epoch
        idx = self._indices()
        nb = len(self)
        chunks = [idx[i * self.batch_size:(i + 1) * self.batch_size]
                  for i in range(nb)]
        if self._fell_back:
            yield from self._fallback_batches(chunks, 0, epoch)
            return
        if not self._procs:
            try:
                self._start_pool()
            except Exception as e:  # noqa: BLE001 — spawn can fail (fd/mem)
                print("process loader: pool start failed (%s); falling back "
                      "to the thread loader" % e, flush=True)
                self._fell_back = True
                yield from self._fallback_batches(chunks, 0, epoch)
                return

        self._iter_seq += 1
        seg_name = lambda bi: "%s_i%d_b%d" % (self._prefix,  # noqa: E731
                                              self._iter_seq, bi)
        # Dispatch window = how many batches are in flight (queued or being
        # built). Concurrent execution beyond the physical cores only adds
        # context-switch + cache thrash (measured: 2 workers on the 1-core
        # bench box ran at 0.8x of 1 worker), so the concurrency term is
        # clamped to the core count; queue headroom on top keeps workers
        # fed, except on a 1-core host where any second in-flight task IS
        # concurrent execution.
        cores = os.cpu_count() or 1
        concurrency = max(1, min(self.num_workers, cores))
        headroom = max(1, self.prefetch) if cores > 1 else 0
        window = concurrency + headroom
        outstanding = {}    # batch_idx -> segment name (dispatched, unmapped)
        ready = {}          # batch_idx -> Batch (mapped, awaiting in-order emit)
        next_dispatch = 0
        next_emit = 0
        clean = False
        try:
            while next_emit < nb:
                while len(outstanding) < window and next_dispatch < nb:
                    name = seg_name(next_dispatch)
                    outstanding[next_dispatch] = name
                    self._task_q.put((next_dispatch, epoch, name,
                                      chunks[next_dispatch]))
                    next_dispatch += 1
                if next_emit in ready:
                    batch = ready.pop(next_emit)
                    bi_emit = next_emit
                    next_emit += 1
                    if self._quarantine_batch(batch, bi_emit, epoch):
                        continue
                    yield batch
                    continue
                try:
                    kind, bi, name, payload, infos = \
                        self._result_q.get(timeout=0.5)
                except queue_mod.Empty:
                    dead = [i for i, p in enumerate(self._procs)
                            if not p.is_alive()]
                    if dead:
                        print("process loader: worker(s) %s died; reaping "
                              "pool and falling back to the thread loader "
                              "for the rest of the run" % dead, flush=True)
                        self._fell_back = True
                        self._stop_pool()
                        yield from self._fallback_batches(chunks, next_emit,
                                                          epoch)
                        clean = True
                        return
                    continue
                if kind == "err":
                    raise RuntimeError(
                        "process loader worker failed:\n%s" % payload)
                ready[bi] = _map_batch(payload, name, infos)
                # name gone immediately: the mapped pages outlive it and a
                # consumer crash can no longer leak the segment
                _unlink_segment(name)
                outstanding.pop(bi, None)
            clean = True
        finally:
            if not clean:
                # consumer abandoned mid-epoch (break / exception): queued
                # tasks and in-flight segments are stale — reset the pool
                # (its sweep destroys every segment under this prefix)
                self._stop_pool()
            else:
                for name in outstanding.values():  # err-raise leftovers
                    _unlink_segment(name)


def _cleanup(procs, prefix: str, task_q, result_q) -> None:
    """Tear down a pool: terminate workers, drain queues, sweep segments.
    Module-level (not a bound method) so `weakref.finalize` never keeps
    the loader alive. The prefix sweep destroys every segment this loader
    ever created that still has a name — including ones a SIGKILLed
    worker was mid-write in."""
    for p in procs:
        if p.is_alive():
            p.terminate()
    for p in procs:
        p.join(timeout=5)
    for q in (task_q, result_q):
        if q is not None:
            try:
                q.close()
                q.cancel_join_thread()
            except Exception:  # noqa: BLE001
                pass
    try:
        import glob
        for path in glob.glob(os.path.join(_SHM_DIR, prefix + "*")):
            _unlink_segment(os.path.basename(path))
    except OSError:
        pass
