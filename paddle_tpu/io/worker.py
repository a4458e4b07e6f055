"""Multiprocess DataLoader workers over shared-memory rings.

Reference parity: python/paddle/io/dataloader/worker.py + the shared-
memory queue transport (unverified, mount empty): forked worker
processes fetch+collate batches and pass them to the parent without
pickling the payload.

TPU design notes:
- Workers are SPAWNED (fork+exec of a fresh interpreter), not forked:
  the training process is heavily multithreaded (jax/XLA thread pools),
  and a bare fork() inherits their locked mutexes — measured deadlocks,
  sometimes after the child had already produced batches. The spawn
  bootstrap loads ONLY numpy + this module, so workers never touch
  jax; the dataset/collate_fn/indices ship via one pickle file.
- Batch i is produced by worker i % num_workers and the parent reads
  rings round-robin, preserving the reference's deterministic ordering.
- Record format: [u32 magic][u32 header_len][pickled (spec, leaf_meta)]
  [64-aligned raw array bytes...]. Only the structure is pickled; the
  array payload is memcpy'd once in the worker and viewed in the parent.
"""
from __future__ import annotations

import os
import pickle
import struct
import sys

import numpy as np

_MAGIC = 0x50445452  # "PDTR"
_ALIGN = 64


def _align(n):
    return (n + _ALIGN - 1) & ~(_ALIGN - 1)


def _is_paddle_tensor(x):
    return hasattr(x, "value") and hasattr(x, "stop_gradient")


def collate_numpy(batch):
    """default_collate_fn semantics with numpy leaves (worker-side).
    Paddle-Tensor samples are materialized to numpy — safe in a SPAWNED
    worker (its private jax runtime was created in this process, on CPU)."""
    sample = batch[0]
    if _is_paddle_tensor(sample):
        return np.stack([np.asarray(s.numpy()) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(sample, (list, tuple)):
        return tuple(
            collate_numpy(list(col)) for col in zip(*batch)
        )
    if isinstance(sample, dict):
        return {k: collate_numpy([d[k] for d in batch]) for k in sample}
    if isinstance(sample, (str, bytes)):
        return list(batch)
    return np.stack([np.asarray(s) for s in batch])


def serialize_batch(batch):
    """-> one bytes record: pickled structure + raw aligned array bytes."""
    leaves = []

    def enc(x):
        if _is_paddle_tensor(x):
            x = np.asarray(x.numpy())
        if isinstance(x, np.ndarray):
            leaves.append(np.ascontiguousarray(x))
            return ("a", len(leaves) - 1)
        if isinstance(x, tuple):
            return ("t", [enc(v) for v in x])
        if isinstance(x, list):
            return ("l", [enc(v) for v in x])
        if isinstance(x, dict):
            return ("d", {k: enc(v) for k, v in x.items()})
        return ("o", x)

    spec = enc(batch)
    meta = [(l.dtype.str, l.shape, l.nbytes) for l in leaves]
    header = pickle.dumps((spec, meta), protocol=pickle.HIGHEST_PROTOCOL)
    off = _align(8 + len(header))
    offsets = []
    for l in leaves:
        offsets.append(off)
        off = _align(off + l.nbytes)
    buf = bytearray(off)
    struct.pack_into("<II", buf, 0, _MAGIC, len(header))
    buf[8 : 8 + len(header)] = header
    for l, o in zip(leaves, offsets):
        buf[o : o + l.nbytes] = l.tobytes()  # one worker-side copy
    return bytes(buf)


def deserialize_batch(view, to_leaf):
    """Rebuild the structure from a record view; array leaves become
    ``to_leaf(np_view)`` where np_view is ZERO-COPY into the ring."""
    magic, hlen = struct.unpack_from("<II", view, 0)
    if magic != _MAGIC:
        raise ValueError("corrupt DataLoader record")
    spec, meta = pickle.loads(bytes(memoryview(view)[8 : 8 + hlen]))
    off = _align(8 + hlen)
    arrays = []
    for dtype, shape, nbytes in meta:
        arr = np.frombuffer(view, dtype=np.dtype(dtype), count=int(
            np.prod(shape)) if shape else 1, offset=off).reshape(shape)
        arrays.append(arr)
        off = _align(off + nbytes)

    def dec(node):
        kind = node[0]
        if kind == "a":
            return to_leaf(arrays[node[1]])
        if kind == "t":
            return tuple(dec(v) for v in node[1])
        if kind == "l":
            return [dec(v) for v in node[1]]
        if kind == "d":
            return {k: dec(v) for k, v in node[1].items()}
        return node[1]

    return dec(spec)


def _load_shmring():
    """ShmRing class, resolvable both in-package and from the spawn
    bootstrap (where this module is loaded by file path with no parent
    package — importing paddle_tpu/__init__ would drag in jax)."""
    try:
        from ..native import ShmRing

        return ShmRing
    except ImportError:
        import importlib.util

        p = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), os.pardir,
            "native", "__init__.py",
        )
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu_native_standalone", p
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.ShmRing


def spawn_main():
    """Entry point of a SPAWNED worker: argv[1] is a pickle file holding
    (main_script, inner) where inner unpickles to worker_loop's
    positional args: (ring_name, dataset, collate_fn, index_batches,
    worker_id, worker_init_fn, num_workers, base_seed).

    Datasets/collate_fns defined in the training script itself pickle as
    ``__main__.X``; like multiprocessing's spawn, the parent's main
    script is re-imported here under ``__mp_main__`` and aliased to
    ``__main__`` so those names resolve. The script runs with
    __name__ != "__main__", so the standard ``if __name__ == "__main__"``
    guard keeps its training entry from re-executing."""
    # the outer payload holds (main_script, inner_pickle): the alias must
    # be installed BEFORE the inner args (which may reference __main__
    # classes) are unpickled
    with open(sys.argv[1], "rb") as f:
        main_script, blob = pickle.load(f)
    if main_script and os.path.exists(main_script):
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "__mp_main__", main_script
            )
            m = importlib.util.module_from_spec(spec)
            sys.modules["__mp_main__"] = m
            spec.loader.exec_module(m)
            sys.modules["__main__"] = m
        except BaseException:
            pass  # unpickle below will fail with a shipped error if needed
    worker_loop(*pickle.loads(blob))


class WorkerInfo:
    """paddle.io.get_worker_info() payload (reference:
    python/paddle/io/dataloader/worker.py WorkerInfo — unverified).
    ``seed`` follows the reference contract: base_seed + worker id, for
    per-worker RNG seeding in datasets/worker_init_fn."""

    def __init__(self, id, num_workers, dataset, seed=None):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = (0 if seed is None else seed) + id

    def __repr__(self):
        return (
            f"WorkerInfo(id={self.id}, num_workers={self.num_workers}, "
            f"seed={self.seed})"
        )


_WORKER_INFO = None
# thread-pool fallback refcount (see dataloader._iter_prefetch)
import threading as _threading  # noqa: E402

_FALLBACK_LOCK = _threading.Lock()
_FALLBACK_DEPTH = [0]


def get_worker_info():
    """Inside a DataLoader worker process: that worker's WorkerInfo
    (id / num_workers / dataset); in the main process: None."""
    return _WORKER_INFO


def worker_loop(ring_name, dataset, collate_fn, index_batches, worker_id,
                worker_init_fn=None, num_workers=None, base_seed=None):
    """Worker-process entry: fetch assigned batches in order, write to
    the per-worker ring, close the ring when done (or on error, after
    shipping the exception). NOTHING may escape this function — it
    always terminates the process via os._exit."""
    try:
        ShmRing = _load_shmring()

        ring = ShmRing(ring_name, create=False)
    except BaseException:
        os._exit(1)
    try:
        # startup handshake: fork-from-a-threaded-parent can deadlock the
        # child before it runs a single line (inherited locked mutexes —
        # jax is multithreaded); the parent waits for this record with a
        # timeout and falls back to the thread pool if it never arrives
        ring.write(b"HELLO")
        global _WORKER_INFO
        _WORKER_INFO = WorkerInfo(worker_id, num_workers, dataset, base_seed)
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        for indices in index_batches:
            samples = [dataset[i] for i in indices]
            batch = (collate_fn or collate_numpy)(samples)
            ring.write(serialize_batch(batch))
        ring.close()
    except BrokenPipeError:
        pass  # parent tore down mid-epoch
    except BaseException as e:  # ship the failure to the parent
        try:
            import traceback

            msg = pickle.dumps(
                ("error", f"{type(e).__name__}: {e}\n"
                 + "".join(traceback.format_exc()))
            )
            ring.write(b"\xff\xff\xff\xff" + msg)
            ring.close()
        except Exception:
            pass
        os._exit(1)
    finally:
        ring.detach()
    os._exit(0)
