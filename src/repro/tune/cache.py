"""Content-addressed on-disk tune cache + JAX persistent-cache wiring.

Layout (default root ``<checkout>/.cache/tune``, overridable with the
``REPRO_TUNE_CACHE_DIR`` env var or the ``tune_cache_dir=`` argument):

    <root>/kernels/<sha>.json    one entry per KernelSig x kernel-version:
                                 the winning blocks + search telemetry.
                                 Shared across graphs — two models hitting
                                 the same (family, shapes, bits, requant,
                                 backend) workload share one search.
    <root>/graphs/<sha>.json     per-graph manifest: sig-key -> blocks, so
                                 a warm reload answers every segment from
                                 ONE file read instead of one per segment.

The JAX persistent compilation cache lives apart from it, at
``$JAX_COMPILATION_CACHE_DIR`` when set and ``<checkout>/.cache/jax``
otherwise (``configure_jax_persistent_cache``, called by every
``compile_graph``): a fixed path, because the path is part of what JAX
keys a cached executable by.

Keys are content hashes:

  * kernel entry  — sha256(KernelSig canonical JSON + kernel_version()),
    where ``kernel_version`` digests every ``src/repro/kernels/*.py``
    source file.  Editing any kernel silently invalidates every entry (the
    old files stay behind as dead weight, never wrong answers).
  * graph manifest — sha256(graph_hash + backend + kernel_version), where
    ``graph_hash`` digests the deterministic ``serialize.graph_to_json``
    form: weights, shapes, bit widths, topology.  Any model edit is a
    clean miss, never a stale hit.

Robustness contract: the cache can be deleted, truncated, corrupted or
raced at any time and the worst case is a re-search — ``lookup_*`` returns
None on any decode error (unlinking the bad file best-effort), writes are
atomic (tmp file in the same dir + ``os.replace``) so a concurrent reader
never sees a half-written entry and the last concurrent writer wins
whole-file.
"""
from __future__ import annotations

import functools
import glob
import hashlib
import json
import logging
import os
import tempfile
from typing import Optional

from .config import BlockConfig, KernelSig

log = logging.getLogger("repro.tune")

_ENV_VAR = "REPRO_TUNE_CACHE_DIR"
# <checkout>/.cache: src/repro/tune/cache.py is three levels below it
_CACHE_HOME = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), ".cache")
_DEFAULT_ROOT = os.path.join(_CACHE_HOME, "tune")
JAX_CACHE_DIR = os.path.join(_CACHE_HOME, "jax")


@functools.lru_cache(maxsize=1)
def kernel_version() -> str:
    """sha256 over all kernel sources — the tune-entry version stamp."""
    kern_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "kernels")
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(kern_dir, "*.py"))):
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def graph_hash(graph) -> str:
    """sha256 of the graph's deterministic serialized form.

    ``serialize.graph_to_json`` embeds initializers (weights), shapes,
    quantizer bit widths and topology, so any change to any of them changes
    the hash — the invalidation the tests assert.
    """
    from repro.core.serialize import graph_to_json
    doc = json.dumps(graph_to_json(graph), sort_keys=True)
    return hashlib.sha256(doc.encode()).hexdigest()


def graph_cache_key(graph, backend: str = "cpu") -> str:
    """Manifest key: graph content x timing backend x kernel sources."""
    h = hashlib.sha256()
    h.update(graph_hash(graph).encode())
    h.update(backend.encode())
    h.update(kernel_version().encode())
    return h.hexdigest()


def _atomic_write_json(path: str, doc: dict) -> None:
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _read_json(path: str) -> Optional[dict]:
    """Load a cache file; any failure (missing, truncated, corrupt, not a
    dict) is a miss.  Corrupt files are unlinked best-effort so they don't
    mask future stores."""
    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            raise ValueError("cache entry is not an object")
        return doc
    except FileNotFoundError:
        return None
    except (OSError, ValueError):
        try:
            os.unlink(path)
        except OSError:
            pass
        return None


class TuneCache:
    """The on-disk tiling store (see module docstring for layout/keys)."""

    def __init__(self, root: Optional[str] = None):
        root = root or os.environ.get(_ENV_VAR) or _DEFAULT_ROOT
        self.root = os.path.abspath(os.path.expanduser(root))
        self.kernels_dir = os.path.join(self.root, "kernels")
        self.graphs_dir = os.path.join(self.root, "graphs")

    # -- kernel entries (shared across graphs) -------------------------
    def _kernel_path(self, sig: KernelSig) -> str:
        h = hashlib.sha256()
        h.update(sig.canonical_json().encode())
        h.update(kernel_version().encode())
        return os.path.join(self.kernels_dir, h.hexdigest() + ".json")

    def lookup_kernel(self, sig: KernelSig) -> Optional[BlockConfig]:
        doc = _read_json(self._kernel_path(sig))
        if doc is None:
            return None
        try:
            blocks = tuple(int(b) for b in doc["blocks"])
        except (KeyError, TypeError, ValueError):
            return None
        return BlockConfig(blocks=blocks, source="cached")

    def store_kernel(self, sig: KernelSig, blocks, *,
                     best_ms: Optional[float] = None,
                     n_candidates: Optional[int] = None) -> None:
        doc = {"sig": json.loads(sig.canonical_json()),
               "blocks": [int(b) for b in blocks],
               "kernel_version": kernel_version()}
        if best_ms is not None:
            doc["best_ms"] = round(float(best_ms), 6)
        if n_candidates is not None:
            doc["n_candidates"] = int(n_candidates)
        _atomic_write_json(self._kernel_path(sig), doc)

    # -- per-graph manifests -------------------------------------------
    def _graph_path(self, graph_key: str) -> str:
        return os.path.join(self.graphs_dir, graph_key + ".json")

    def load_manifest(self, graph_key: str) -> Optional[dict]:
        """sig-key -> blocks mapping for a previously tuned graph."""
        doc = _read_json(self._graph_path(graph_key))
        if doc is None:
            return None
        mapping = doc.get("segments")
        if not isinstance(mapping, dict):
            return None
        out = {}
        try:
            for key, blocks in mapping.items():
                out[key] = tuple(int(b) for b in blocks)
        except (TypeError, ValueError):
            return None
        return out

    def store_manifest(self, graph_key: str, mapping: dict) -> None:
        doc = {"kernel_version": kernel_version(),
               "segments": {k: [int(b) for b in v]
                            for k, v in mapping.items()}}
        _atomic_write_json(self._graph_path(graph_key), doc)


_jax_cache_configured: list = []            # once-per-process latch


def configure_jax_persistent_cache(
        cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache at a fixed directory.

    Jitted executables then survive process restarts — the second serve of
    the same model skips XLA compilation entirely.  A set
    ``JAX_COMPILATION_CACHE_DIR`` is used as JAX reads it, and no other
    directory is set; otherwise ``cache_dir`` or ``<checkout>/.cache/jax``.
    The thresholds are dropped to 0/-1 because quantized-inference
    executables are small but recompiled often.  Once per process: JAX
    ignores config churn after first use, so later calls return the
    already-configured dir.  A directory that cannot be set up is logged
    with its cause and compilation stays in-memory (returns None).
    """
    if _jax_cache_configured:
        return _jax_cache_configured[0]
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env_dir or cache_dir or JAX_CACHE_DIR
    try:
        import jax
        os.makedirs(path, exist_ok=True)
        if not env_dir:
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    except Exception:
        log.warning("JAX persistent compilation cache at %s could not be "
                    "set up; compiling in memory only", path, exc_info=True)
        _jax_cache_configured.append(None)
        return None
    _jax_cache_configured.append(path)
    return path
