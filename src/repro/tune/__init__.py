"""repro.tune — kernel autotuner + persistent compilation cache.

The compiled tier historically ran every Pallas kernel at module-constant
block sizes.  This package makes tiling a per-workload decision with
memory:

  * ``roofline``  — tile cost models (VMEM footprint, modeled HBM traffic)
                    and Pareto pruning, sharing machine constants with
                    ``benchmarks/roofline.py``.
  * ``config``    — ``KernelSig`` (content-addressed workload identity:
                    family x shape bucket x carrier bits x requant path x
                    backend) and ``BlockConfig`` (chosen tiling +
                    provenance).
  * ``cache``     — ``TuneCache``: atomic, corrupt-tolerant on-disk store
                    (``<checkout>/.cache/tune`` / ``$REPRO_TUNE_CACHE_DIR``)
                    of per-kernel entries and per-graph manifests, keyed by
                    content hashes that fold in ``kernel_version()``; plus
                    ``configure_jax_persistent_cache`` so jitted
                    executables survive process restarts.
  * ``autotuner`` — ``Autotuner``: the oracle ``compile_graph(tune=...)``
                    threads through the lowering rules; answers from the
                    manifest, the shared cache, or (mode "search") a
                    roofline-pruned best-of-N measurement of the real
                    kernels.

Entry points: ``compile_graph(graph, tune="cached"|"search")``,
``python -m repro.launch.serve --tune ...``, and
``python -m benchmarks.bench_compile --check-tune MODEL`` (the CI gate).
"""
from .autotuner import Autotuner  # noqa: F401
from .cache import (  # noqa: F401
    TuneCache, configure_jax_persistent_cache, graph_cache_key, graph_hash,
    kernel_version)
from .config import BlockConfig, KernelSig, bucket_rows  # noqa: F401
from . import roofline  # noqa: F401

__all__ = [
    "Autotuner",
    "BlockConfig",
    "KernelSig",
    "TuneCache",
    "bucket_rows",
    "configure_jax_persistent_cache",
    "graph_cache_key",
    "graph_hash",
    "kernel_version",
    "roofline",
]
