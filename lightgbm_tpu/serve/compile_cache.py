"""Persistent AOT compile cache: zero cold-start serving (ISSUE-12).

PR 10's ``compile.end`` telemetry itemized what a serving process pays on
every restart or hot model swap: one XLA compile per (program, ladder
rung) — tens of seconds of p99 cliff before the first warm request.  This
module makes that cost once-per-fleet instead of once-per-process: each
compiled predict executable is serialized (``jax.experimental
.serialize_executable``) into a checksummed frame on disk
(``serialization.write_atomic_frame`` — the PR-6 atomic-write/checksum
helpers), keyed by

    sha256(plan identity | program kind | padded batch rows
           | jax + jaxlib version | backend)

where *plan identity* digests the pack/table array bytes plus the
quantize/traverse modes — the same model served at the same rung hits; a
retrained model, a different slice, a different quantize mode, or a
jaxlib upgrade misses by construction (stale entries can never load).

Hygiene: a corrupt frame (torn write, bitrot) fails the checksum, is
warned about, unlinked and rebuilt from a fresh compile; entries whose
embedded version tag no longer matches the running jax/jaxlib are swept
by :func:`CompileCache.sweep_stale` (and skipped on load either way).

**Trust boundary**: entries hold serialized executables (machine code)
plus pickled pytree metadata — loading one EXECUTES what the cache dir
contains, exactly like jax's own ``JAX_COMPILATION_CACHE_DIR``.  The
checksum detects corruption, not tampering.  Point the cache only at
directories with the same write-trust as the model files and code
(never world-writable paths); the serving process's filesystem
permissions ARE the security boundary.
Every hit/miss/store/error counts into the telemetry registry under
``compile.aot_cache_*`` and into the owning plan's counters (surfaced by
``ServeMetrics.snapshot`` and the ``BENCH_serve`` blob's restart fields).
"""

from __future__ import annotations

import hashlib
import os
import pickle
from typing import Optional

from ..serialization import (FrameCorruptError, read_frame,
                             write_atomic_frame)
from ..utils.log import Log

ENTRY_SUFFIX = ".aot"
_ENV_DIR = "LIGHTGBM_TPU_SERVE_CACHE_DIR"


def _versions() -> dict:
    import jax
    import jaxlib
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
    }


def cache_dir_for(cfg) -> Optional[str]:
    """Resolve the cache directory: the ``LIGHTGBM_TPU_SERVE_CACHE_DIR``
    env var wins (deploy-time relocation without touching model params),
    else the ``tpu_serve_compile_cache`` config knob; ''/unset disables."""
    path = os.environ.get(_ENV_DIR)
    if path is None:
        path = str(getattr(cfg, "tpu_serve_compile_cache", "") or "")
    return path or None


def entry_key(plan_identity: str, kind: str, padded_rows: int) -> str:
    """Stable entry key; the version tag rides the key so an upgraded
    jax/jaxlib simply misses instead of deserializing garbage."""
    v = _versions()
    raw = (f"{plan_identity}|{kind}|{padded_rows}"
           f"|{v['jax']}|{v['jaxlib']}|{v['backend']}")
    return hashlib.sha256(raw.encode()).hexdigest()


class CompileCache:
    """One on-disk executable cache directory (shared by any number of
    plans/processes — entries are content-keyed and atomically published,
    so concurrent writers only ever race to the same bytes)."""

    def __init__(self, root: str):
        self.root = root
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.errors = 0
        from ..telemetry import registry
        reg = registry()
        self._c_hits = reg.counter("compile.aot_cache_hits")
        self._c_misses = reg.counter("compile.aot_cache_misses")
        self._c_errors = reg.counter("compile.aot_cache_errors")

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key + ENTRY_SUFFIX)

    # ------------------------------------------------------------ load/store
    def load(self, key: str, devices=None):
        """Deserialized compiled executable for ``key``, or None (miss /
        corrupt / version-stale — the latter two unlinked so the caller's
        fresh compile rebuilds the entry).  ``devices`` are the devices
        the executable was compiled for and will run on; jax's default is
        every local device, which on a multi-device host loads a
        one-device program that then demands one argument shard per
        device."""
        path = self._path(key)
        if not os.path.exists(path):
            self.misses += 1
            self._c_misses.inc()
            return None
        try:
            meta, payload, in_tree, out_tree = pickle.loads(read_frame(path))
            if meta.get("versions") != _versions():
                raise FrameCorruptError(
                    f"version-stale entry (built under "
                    f"{meta.get('versions')}, running {_versions()})")
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            compiled = deserialize_and_load(payload, in_tree, out_tree,
                                            execution_devices=devices)
        except Exception as e:  # noqa: BLE001 — any bad entry: warn+rebuild
            self.errors += 1
            self.misses += 1
            self._c_errors.inc()
            self._c_misses.inc()
            Log.warning(
                f"serve compile cache: entry {os.path.basename(path)} "
                f"failed to load ({str(e)[:160]}); removing and "
                "recompiling")
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        self.hits += 1
        self._c_hits.inc()
        return compiled

    def store(self, key: str, compiled) -> bool:
        """Serialize and atomically publish one executable; False (with a
        warning) when the backend cannot serialize it — the cache degrades
        to per-process compiles, it never fails a request."""
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps(
                ({"versions": _versions()}, payload, in_tree, out_tree),
                protocol=4)
            os.makedirs(self.root, exist_ok=True)
            write_atomic_frame(self._path(key), blob)
        except Exception as e:  # noqa: BLE001 — cache is an accelerant only
            self.errors += 1
            self._c_errors.inc()
            Log.warning(f"serve compile cache: could not persist entry "
                        f"({str(e)[:160]}); serving continues uncached")
            return False
        self.stores += 1
        return True

    # --------------------------------------------------------------- hygiene
    def sweep_stale(self) -> dict:
        """Walk the cache dir and drop entries that can never load again:
        corrupt frames (checksum failure) and version-stale executables.
        Returns ``{"kept": n, "removed": n}`` — run it from deploy tooling
        after a jaxlib upgrade so dead bytes don't accumulate."""
        kept = removed = 0
        if not os.path.isdir(self.root):
            return {"kept": 0, "removed": 0}
        for name in os.listdir(self.root):
            if not name.endswith(ENTRY_SUFFIX):
                continue
            path = os.path.join(self.root, name)
            try:
                meta = pickle.loads(read_frame(path))[0]
                if meta.get("versions") != _versions():
                    raise FrameCorruptError("version-stale")
                kept += 1
            except Exception as e:  # noqa: BLE001 — corrupt or stale: drop
                removed += 1
                Log.warning(f"serve compile cache: sweeping {name} "
                            f"({str(e)[:120]})")
                try:
                    os.unlink(path)
                except OSError:
                    pass
        return {"kept": kept, "removed": removed}

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "stores": self.stores, "errors": self.errors}
