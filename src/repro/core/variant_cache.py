"""Persistent variant cache: serialized AOT executables across process runs.

The paper's online search pays an XLA compile per candidate; §6.4 measures
exactly that cost (Table 4) and "Towards Online Code Specialization of
Systems" (PAPERS.md) motivates caching specialized artifacts across runs.
This module makes variant *generation* free on warm restart: every AOT
executable the runtime compiles is serialized to disk
(``jax.experimental.serialize_executable``), and a fresh process that asks
for the same (handler, config, argument specs, backend) gets the loaded
executable back with **zero recompiles**.

Key schema (any component changing invalidates the entry):

    (cache format version, handler name, config_key, instrumented flag,
     jit kwargs, argument-spec fingerprint, backend platform, device kind,
     device count, jax version)

hashed to one file ``<dir>/<sha256>.var``.  Writes are atomic
(tempfile + rename) so a crash mid-store never corrupts an entry; loads
fall back gracefully — any deserialization failure logs a warning, deletes
the bad entry, and the caller just recompiles.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
import threading
from typing import Any

import jax

from repro.core.metrics import AtomicCounter

logger = logging.getLogger("repro.core.variant_cache")

__all__ = ["VariantCache", "spec_fingerprint", "backend_fingerprint"]

_FORMAT_VERSION = 2
_SUFFIX = ".var"


def _describe_leaf(x: Any) -> str:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        sharding = getattr(x, "sharding", None)
        return f"{x.dtype}{tuple(x.shape)}@{sharding}"
    return f"py:{x!r}"


def spec_fingerprint(args: tuple, kwargs: dict) -> str:
    """Canonical string for a (possibly abstract) argument pytree."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return f"{treedef}|{';'.join(_describe_leaf(x) for x in leaves)}"


def backend_fingerprint(portable: bool = False) -> str:
    """Backend component of the cache key.

    ``portable=True`` drops the device *count* (keeping platform, device
    kind, and jax version), so artifacts compiled on one host warm-start N
    identical replicas — see :class:`VariantCache` for the safety
    tradeoff.
    """
    devs = jax.devices()
    count = "*" if portable else str(len(devs))
    return (f"{jax.default_backend()}|{devs[0].device_kind}|{count}"
            f"|jax-{jax.__version__}")


class CacheStats:
    """Lock-free counters (loads/stores run on concurrent compile workers)."""

    __slots__ = ("hits", "misses", "stores", "errors", "evictions")

    def __init__(self):
        self.hits = AtomicCounter()
        self.misses = AtomicCounter()
        self.stores = AtomicCounter()
        self.errors = AtomicCounter()
        self.evictions = AtomicCounter()

    def as_dict(self) -> dict:
        return {name: getattr(self, name).value() for name in self.__slots__}


class VariantCache:
    """Disk cache of serialized AOT executables (see module docstring).

    ``max_bytes`` caps the on-disk size: when an insert pushes the total
    over the cap, the least-recently-used entries (by file mtime — loads
    touch their entry, so mtime tracks last use, not last write) are
    evicted until the cache fits again.  ``None`` = unbounded.

    ``portable=True`` drops the device **count** from the entry key
    (platform, device kind, and jax version stay pinned), so a cache
    populated on a single host warm-starts N identical replicas behind a
    shared artifact store.  The safety tradeoff: an executable whose
    compiled program *depends* on the device count (multi-device sharding,
    collectives) may deserialize on a host where that count is wrong — the
    load then fails (deleted + recompiled, the normal corrupt-entry path)
    or, for programs XLA considers loadable, runs with the original
    partitioning.  Only enable it for fleets of replicas with identical
    per-host topology; the default stays pinned to the exact device count.
    """

    def __init__(self, directory: str, max_bytes: int | None = None,
                 portable: bool = False):
        self.directory = str(directory)
        self.max_bytes = max_bytes
        self.portable = bool(portable)
        os.makedirs(self.directory, exist_ok=True)
        self._lock = threading.Lock()
        self.stats = CacheStats()
        self._serialize_broken = False   # set when the host can't serialize

    # -- keys -----------------------------------------------------------------
    def entry_key(self, handler_name: str, config_key: tuple,
                  instrumented: bool, jit_kwargs: Any,
                  arg_fingerprint: str) -> str:
        raw = repr((_FORMAT_VERSION, handler_name, config_key,
                    bool(instrumented), sorted(repr(i) for i in
                                               dict(jit_kwargs or {}).items()),
                    arg_fingerprint, backend_fingerprint(self.portable)))
        return hashlib.sha256(raw.encode()).hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + _SUFFIX)

    # -- load / store ----------------------------------------------------------
    def load(self, key: str) -> Any | None:
        """Return the loaded executable, or None on miss / corrupt entry."""
        path = self._path(key)
        if not os.path.exists(path):
            self.stats.misses.bump()
            return None
        try:
            from jax.experimental import serialize_executable
            with open(path, "rb") as f:
                entry = pickle.load(f)
            blob, in_tree, out_tree = entry["payload"]
            # Load onto the devices it was compiled for: by default JAX
            # would spread a one-device executable over every local device.
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[by_id[i] for i in entry["device_ids"]])
            self.stats.hits.bump()
            try:
                os.utime(path, None)     # refresh last_used for LRU eviction
            except OSError:
                pass
            return compiled
        except Exception as e:
            # Corrupt / stale / cross-version entry: drop it and recompile.
            self.stats.errors.bump()
            self.stats.misses.bump()
            logger.warning("variant cache entry %s unreadable (%s: %s); "
                           "deleting and recompiling", key,
                           type(e).__name__, e)
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    def store(self, key: str, compiled: Any, meta: dict | None = None) -> bool:
        """Serialize ``compiled`` under ``key``; atomic, best-effort."""
        if self._serialize_broken:
            return False
        try:
            from jax.experimental import serialize_executable
            payload = serialize_executable.serialize(compiled)
            entry = {"format": _FORMAT_VERSION,
                     "backend": backend_fingerprint(self.portable),
                     "meta": dict(meta or {}),
                     "device_ids": [d.id for d in compiled.runtime_executable()
                                    .local_devices()],
                     "payload": payload}
            blob = pickle.dumps(entry)
        except Exception as e:
            # Unsupported executable / backend: disable stores, keep serving.
            self.stats.errors.bump()
            if not self._serialize_broken:
                logger.warning("variant serialization unavailable "
                               "(%s: %s); persistent cache disabled for "
                               "stores", type(e).__name__, e)
            self._serialize_broken = True
            return False
        path = self._path(key)
        with self._lock:
            tmp = None
            try:
                # distinct suffix: a crash mid-store must not leave a file
                # that entries()/load() would mistake for a real entry
                fd, tmp = tempfile.mkstemp(dir=self.directory,
                                           prefix=".tmp_", suffix=".part")
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)            # atomic publish
            except OSError as e:
                self.stats.errors.bump()
                logger.warning("variant cache store failed for %s: %s",
                               key, e)
                if tmp is not None:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                return False
            if self.max_bytes is not None:
                self._evict_lru_locked(keep=path)
        self.stats.stores.bump()
        return True

    def _evict_lru_locked(self, keep: str | None = None) -> int:
        """Evict least-recently-used entries until the cache fits
        ``max_bytes``.  The just-written entry (``keep``) survives even when
        it alone exceeds the cap — evicting what was just stored would make
        the cache useless for oversized-but-only entries."""
        entries = []
        for name in os.listdir(self.directory):
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.directory, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, path))
        total = sum(size for _, size, _ in entries)
        evicted = 0
        for _, size, path in sorted(entries):   # oldest last_used first
            if total <= self.max_bytes:
                break
            if path == keep:
                continue
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            evicted += 1
            self.stats.evictions.bump()
            logger.info("variant cache evicted LRU entry %s (%d bytes)",
                        os.path.basename(path), size)
        return evicted

    # -- maintenance -----------------------------------------------------------
    def entries(self) -> list[str]:
        return sorted(n[:-len(_SUFFIX)] for n in os.listdir(self.directory)
                      if n.endswith(_SUFFIX))

    def clear(self) -> None:
        for key in self.entries():
            try:
                os.unlink(self._path(key))
            except OSError:
                pass
