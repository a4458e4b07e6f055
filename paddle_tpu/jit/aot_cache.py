"""Persistent AOT compile cache — serialized XLA executables on disk.

The serving engines compile a small, fully-enumerable set of
fixed-shape programs (one decode step, one prefill + one adopt per
power-of-two prompt bucket — ``analysis.TraceGuard`` inventories
exactly these entries at runtime). Cold start therefore pays one XLA
compile per program at first traffic: seconds of wall clock per bucket
while the chip idles, multiplied by every relaunch and every newly
spawned replica. This module makes those compiles a one-time cost per
(program, geometry, device-kind):

- ``engine.warmup(aot_cache=dir)`` lowers + compiles every program
  BEFORE first traffic and serializes each finished executable here
  (``jax.experimental.serialize_executable`` — the PjRt executable
  blob plus its arg/result trees, pickled and written atomically);
- a relaunched or newly spawned replica with the same cache dir
  deserializes the executables instead of tracing or compiling
  anything: it reaches READY with zero new trace-guard compile
  entries, and its first request runs the exact same binary the
  previous process ran.

Keys hash the full program identity: engine geometry + model dims +
sampling config, the aval signature (shape/dtype of every leaf plus
the pytree structure), jax version, backend platform and device kind —
any drift is a clean MISS, never a wrong executable. A corrupt or
unreadable entry degrades to a cold compile (counted, one warning),
mirroring the kernel tune cache's discipline. The conventional
location is ``aot_cache/`` next to ``jit.save`` artifacts or inside a
checkpoint root (:func:`cache_dir_for`).

Cache hits/misses/saves publish as
``paddle_jit_aot_cache_total{event=...}``.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading

import jax

logger = logging.getLogger("paddle_tpu.jit.aot_cache")

MANIFEST_FILE = "manifest.json"


def cache_dir_for(artifact_or_ckpt_dir):
    """The conventional AOT cache location next to saved artifacts or
    inside a checkpoint root."""
    return os.path.join(str(artifact_or_ckpt_dir), "aot_cache")


def _aval_signature(args):
    """(pytree structure repr, per-leaf shape/dtype) — the part of a
    program's identity its example arguments carry."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    avals = []
    for leaf in leaves:
        shape = list(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        avals.append([shape, dtype])
    return {"tree": str(treedef), "avals": avals}


def _count(event):
    try:
        from ..observability import get_registry

        get_registry().counter(
            "paddle_jit_aot_cache_total",
            help="AOT compile-cache events (hit|miss|save|error)",
        ).inc(event=event)
    except Exception:
        pass


class AOTProgramCache:
    """Directory of serialized executables + a JSON manifest.

    The manifest (``manifest.json``) is the human/tooling inventory:
    one record per entry with the program name, aval signature and
    provenance. It is advisory — entry files are self-contained, and a
    concurrent writer losing a manifest read-modify-write race costs
    only an inventory line, never a wrong load."""

    def __init__(self, path):
        self.path = str(path)
        os.makedirs(self.path, exist_ok=True)
        self._lock = threading.Lock()
        self._warned_save = False

    # ----------------------------------------------------------- keying
    def key_for(self, signature, example_args):
        """``(key, meta)`` for a program: ``signature`` is the caller's
        identity dict (engine geometry, model dims, ...), the rest is
        derived — aval signature, jax version, platform, device kind."""
        dev = jax.devices()[0]
        meta = {
            "signature": signature,
            "args": _aval_signature(example_args),
            "jax": jax.__version__,
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", "unknown"),
        }
        key = hashlib.sha256(
            json.dumps(meta, sort_keys=True).encode("utf-8")
        ).hexdigest()[:32]
        return key, meta

    def _entry_path(self, key):
        return os.path.join(self.path, f"{key}.aotx")

    def has(self, key):
        return os.path.isfile(self._entry_path(key))

    # ------------------------------------------------------------ load
    def load(self, key):
        """Deserialize + load the executable for ``key``, or None on
        miss/corruption (a bad entry is removed and counted — the
        caller falls back to a cold compile)."""
        p = self._entry_path(key)
        if not os.path.isfile(p):
            _count("miss")
            return None
        try:
            from jax.experimental import serialize_executable as se

            with open(p, "rb") as f:
                *parts, device_ids = pickle.load(f)
            # load onto the devices the program was compiled for: left
            # to its default, jax spreads a one-device program over
            # every local device and refuses its one-shard arguments
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                *parts,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception as e:
            _count("error")
            logger.warning(
                "aot cache: entry %s unusable (%r); recompiling", p, e
            )
            try:
                os.remove(p)
            except OSError:
                pass
            return None
        _count("hit")
        return compiled

    # ------------------------------------------------------------ save
    def save(self, key, compiled, meta):
        """Serialize ``compiled`` under ``key`` (atomic write) and add
        its manifest record. Returns True on success; failures degrade
        to not-cached (counted, warned once)."""
        try:
            from jax.experimental import serialize_executable as se

            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
            blob = pickle.dumps((*se.serialize(compiled), device_ids))
            fd, tmp = tempfile.mkstemp(
                dir=self.path, suffix=".aotx.tmp"
            )
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, self._entry_path(key))
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
            self._note_entry(key, meta, len(blob))
        except Exception as e:
            _count("error")
            if not self._warned_save:
                self._warned_save = True
                logger.warning(
                    "aot cache: cannot serialize executables on this "
                    "backend (%r); warmup still compiles, nothing is "
                    "persisted", e
                )
            return False
        _count("save")
        return True

    # -------------------------------------------------------- manifest
    def _manifest_path(self):
        return os.path.join(self.path, MANIFEST_FILE)

    def entries(self):
        """The manifest inventory ``{key: record}`` ({} when absent)."""
        try:
            with open(self._manifest_path()) as f:
                doc = json.load(f)
            return doc.get("entries", {}) if isinstance(doc, dict) else {}
        except (OSError, ValueError):
            return {}

    def _note_entry(self, key, meta, nbytes):
        with self._lock:
            entries = self.entries()
            entries[key] = {
                "program": (meta.get("signature") or {}).get("program"),
                "bytes": int(nbytes),
                "meta": meta,
            }
            doc = json.dumps({"version": 1, "entries": entries},
                             indent=1, sort_keys=True)
            fd, tmp = tempfile.mkstemp(dir=self.path,
                                       suffix=".manifest.tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    f.write(doc)
                os.replace(tmp, self._manifest_path())
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise


def resolve(cache):
    """Accept an :class:`AOTProgramCache` or a directory path (or
    None); the engine warmup seam calls this so callers can pass
    either."""
    if cache is None or isinstance(cache, AOTProgramCache):
        return cache
    return AOTProgramCache(cache)
