"""Cache-backed per-segment tiling selection (the ``tune=`` compile modes).

``Autotuner.blocks_for(sig)`` is the single question the lowering rules
ask: *which block tuple should this segment's kernel partial carry?*  The
answer resolution order is

  1. the current graph's manifest (one file read per compile, loaded by
     ``begin_graph``),
  2. the shared per-kernel cache entry (another graph already searched
     this exact workload),
  3. mode == "search": measure and remember,
  4. otherwise: the module default, counted as a miss.

The search itself is deliberately cheap-by-construction: candidates come
from a small MXU-aligned lattice, are clamped to the workload's effective
(padded) dims and deduplicated, provably-infeasible tilings (VMEM
footprint over budget) are dropped, Pareto-dominated tilings (another
candidate beats them on both modeled HBM traffic *and* residency —
``tune.roofline``) are dropped, and only the few survivors plus the
module default are actually timed — on synthetic operands, through the
*real* jitted kernel wrappers, with the shared interleaved best-of-N
harness (``obs.profile.time_fns``).  The default is always in the timed
set, so a tuned plan can never select a tiling measured slower than the
default it replaces — the invariant ``bench_compile --check-tune`` gates
on in CI.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .cache import TuneCache
from .config import BlockConfig, KernelSig, bucket_rows
from . import roofline

# Candidate lattices: MXU-aligned multiples of 128 around the defaults.
_MATMUL_BM = (128, 256, 512)
_MATMUL_BN = (128, 256, 512)
_MATMUL_BK = (256, 512, 1024)
_DW_BM = (128, 256, 512)
_DW_BC = (128, 256)
_QDQ_B = (128, 256, 512)


def _defaults():
    from repro.kernels.quant_matmul import DEFAULT_BLOCKS
    from repro.kernels.quant_grouped_conv import DEFAULT_DW_BLOCK
    from repro.kernels.quant_dequant import DEFAULT_BLOCK
    return {"matmul": DEFAULT_BLOCKS, "grouped": DEFAULT_BLOCKS,
            "depthwise": DEFAULT_DW_BLOCK, "qdq": DEFAULT_BLOCK}


class Autotuner:
    """Per-compile tiling oracle over a shared ``TuneCache``.

    mode       — "cached" answers from cache or defaults (never times);
                 "search" additionally measures workloads the cache has
                 never seen.  (mode "off" never constructs an Autotuner.)
    repeats    — best-of-N timing repeats per surviving candidate
    max_candidates — roofline survivors to time (plus the default)
    interpret / backend — threaded into sigs so cache entries from the
                 interpreter never answer for compiled Mosaic and vice
                 versa.
    """

    def __init__(self, cache: Optional[TuneCache] = None, *,
                 mode: str = "cached", repeats: int = 3,
                 max_candidates: int = 4, interpret: bool = True,
                 backend: Optional[str] = None):
        if mode not in ("cached", "search"):
            raise ValueError(f"tune mode must be 'cached' or 'search', "
                             f"got {mode!r}")
        self.cache = cache if cache is not None else TuneCache()
        self.mode = mode
        self.repeats = max(1, int(repeats))
        self.max_candidates = max(1, int(max_candidates))
        self.interpret = bool(interpret)
        if backend is None:
            import jax
            backend = jax.default_backend()
        self.backend = backend
        self.defaults = _defaults()
        self.stats = {"graph_hit": 0, "graph_miss": 0,
                      "hits": 0, "misses": 0, "searched": 0}
        self._graph_key: Optional[str] = None
        self._manifest: dict = {}
        self._manifest_dirty = False

    # ---------------------------------------------------------- manifest
    def begin_graph(self, graph_key: str) -> None:
        """Load the per-graph manifest so warm compiles do one file read."""
        self._graph_key = graph_key
        self._manifest_dirty = False
        loaded = self.cache.load_manifest(graph_key)
        if loaded is not None:
            self._manifest = dict(loaded)
            self.stats["graph_hit"] += 1
        else:
            self._manifest = {}
            self.stats["graph_miss"] += 1

    def end_graph(self) -> None:
        """Persist the manifest if this compile added assignments."""
        if self._graph_key and self._manifest_dirty and self._manifest:
            self.cache.store_manifest(self._graph_key, self._manifest)
        self._graph_key = None
        self._manifest_dirty = False

    # ---------------------------------------------------------- identity
    def sig(self, family: str, *, rows: Optional[int], n: int, k: int,
            groups: int = 1, bits: int = 8,
            requant: str = "fp32") -> KernelSig:
        """Build the content-addressed signature for one segment workload."""
        return KernelSig(family=family, m=bucket_rows(rows), n=int(n),
                         k=int(k), groups=int(groups), bits=int(bits),
                         requant=requant, backend=self.backend,
                         interpret=self.interpret)

    # ---------------------------------------------------------- the oracle
    def blocks_for(self, sig: KernelSig) -> BlockConfig:
        key = sig.canonical_json()
        cached = self._manifest.get(key)
        if cached is not None:
            self.stats["hits"] += 1
            return BlockConfig(blocks=tuple(cached), source="cached")
        entry = self.cache.lookup_kernel(sig)
        if entry is not None:
            self.stats["hits"] += 1
            self._manifest[key] = entry.blocks
            self._manifest_dirty = True
            return entry
        if self.mode == "search":
            cfg = self._search(sig)
            self._manifest[key] = cfg.blocks
            self._manifest_dirty = True
            return cfg
        self.stats["misses"] += 1
        return BlockConfig(blocks=tuple(self.defaults[sig.family]),
                           source="default")

    # ---------------------------------------------------------- search
    def _search(self, sig: KernelSig) -> BlockConfig:
        self.stats["searched"] += 1
        candidates = self._candidates(sig)
        default = self._effective(sig, self.defaults[sig.family])
        if default not in candidates:
            candidates.append(default)
        timings = self._time_candidates(sig, candidates)
        # every candidate failed to build/trace: keep the default
        if not timings:
            self.cache.store_kernel(sig, default)
            return BlockConfig(blocks=default, source="search")
        best_blocks, best_s = min(timings, key=lambda t: t[1])
        self.cache.store_kernel(sig, best_blocks, best_ms=best_s * 1e3,
                                n_candidates=len(timings))
        return BlockConfig(blocks=best_blocks, source="search")

    def _effective(self, sig: KernelSig, blocks) -> tuple:
        """Clamp a candidate exactly the way the kernel wrapper will.

        Distinct lattice points that clamp to the same effective tiling are
        the same workload — deduplicating on the clamped form keeps the
        timed set honest.
        """
        if sig.family in ("matmul", "grouped"):
            m = sig.m
            n = sig.n if sig.family == "matmul" else sig.n  # per-group Ng
            k = sig.k
            bm = min(blocks[0], m)
            bn = min(blocks[1], n)
            bk = min(blocks[2], k)
            if sig.bits == 4 and bk % 2:
                bk += 1
            return (bm, bn, bk)
        if sig.family == "depthwise":
            return (min(blocks[0], sig.m), min(blocks[1], sig.n))
        if sig.family == "qdq":
            return (min(blocks[0], sig.m), min(blocks[1], sig.n))
        raise ValueError(sig.family)

    def _candidates(self, sig: KernelSig) -> list:
        """Clamped, deduped, VMEM-feasible, Pareto-pruned lattice points."""
        if sig.family in ("matmul", "grouped"):
            raw = [(bm, bn, bk) for bm in _MATMUL_BM for bn in _MATMUL_BN
                   for bk in _MATMUL_BK]
            w_bytes = 0.5 if sig.bits == 4 else 1
            seen, eff = set(), []
            for c in raw:
                e = self._effective(sig, c)
                if e not in seen:
                    seen.add(e)
                    eff.append(e)
            eff = [e for e in eff if roofline.matmul_tile_footprint(
                *e, w_bytes=w_bytes) <= roofline.VMEM_BYTES]

            def cost(e):
                traffic = roofline.matmul_tile_traffic(
                    sig.m, sig.n, sig.k, *e, w_bytes=w_bytes)
                if sig.family == "grouped":
                    traffic *= max(1, sig.groups)
                return (traffic, roofline.matmul_tile_footprint(
                    *e, w_bytes=w_bytes))

            return roofline.pareto_prune(eff, cost, self.max_candidates)

        # elementwise families: any tiling moves the same HBM bytes, so the
        # only roofline axis is residency — keep the VMEM-feasible tilings
        # with the fewest grid steps (largest blocks), most-parallel first.
        lattice = ([(bm, bc) for bm in _DW_BM for bc in _DW_BC]
                   if sig.family == "depthwise" else
                   [(bm, bn) for bm in _QDQ_B for bn in _QDQ_B])
        seen, eff = set(), []
        for c in lattice:
            e = self._effective(sig, c)
            if e not in seen:
                seen.add(e)
                eff.append(e)
        eff = [e for e in eff if roofline.elementwise_tile_footprint(*e)
               <= roofline.VMEM_BYTES]
        eff.sort(key=lambda e: -(e[0] * e[1]))
        return eff[:self.max_candidates]

    # ---------------------------------------------------------- timing
    def _time_candidates(self, sig: KernelSig, candidates) -> list:
        """[(blocks, best_seconds)] via the shared interleaved harness.

        Operands are synthetic (seeded) but the callables are the real
        jitted wrappers with the candidate blocks as static args, so the
        measurement includes exactly the padding/blocking behavior the
        compiled plan will see.  Candidates that fail to trace (odd shape
        corners) are dropped rather than failing the compile.
        """
        from repro.obs.profile import time_fns
        fns, kept = [], []
        for blocks in candidates:
            try:
                fns.append(self._make_fn(sig, blocks))
            except Exception:
                continue
            kept.append(blocks)
        if not fns:
            return []
        timed, good_fns, good_blocks = [], [], []
        for fn, blocks in zip(fns, kept):
            try:
                fn()                    # trace+compile probe
            except Exception:
                continue
            good_fns.append(fn)
            good_blocks.append(blocks)
        if not good_fns:
            return []
        times = time_fns(good_fns, self.repeats)
        return list(zip(good_blocks, times))

    def _make_fn(self, sig: KernelSig, blocks):
        from repro.kernels import ops
        from repro.kernels.requant import IntRequant
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        int_requant = sig.requant == "int32"
        requant = IntRequant(shift=8) if int_requant else None
        acc = jnp.int32 if int_requant else jnp.float32
        m, n, k = sig.m, sig.n, sig.k

        if sig.family == "matmul":
            x = rng.randn(m, k).astype(np.float32)
            if int_requant:     # int8 codes, as the lowering feeds the MXU
                x = np.round(x * 8.0).astype(np.int8)
            w = rng.randint(-7, 8, size=(k, n)).astype(np.int8)
            if int_requant:
                scale = np.ones((n,), np.int32)
            else:
                scale = np.ones((n,), np.float32)
            if sig.bits == 4:
                wp = np.asarray(ops.pack_int4(w))
                return lambda: ops.quant_matmul_int4(
                    x, wp, scale, blocks=blocks, interpret=self.interpret,
                    acc_dtype=acc, requant=requant)
            return lambda: ops.quant_matmul(
                x, w, scale, blocks=blocks, interpret=self.interpret,
                acc_dtype=acc, requant=requant)

        if sig.family == "grouped":
            g = max(1, sig.groups)
            xg = rng.randn(g, m, k).astype(np.float32)
            if int_requant:
                xg = np.round(xg * 8.0).astype(np.int8)
            wg = rng.randint(-7, 8, size=(g, k, n)).astype(np.int8)
            if int_requant:
                scale = np.ones((g * n,), np.int32)
            else:
                scale = np.ones((g * n,), np.float32)
            if sig.bits == 4:
                wgp = np.asarray(ops.pack_int4_grouped(wg))
                return lambda: ops.quant_grouped_matmul(
                    xg, wgp, scale, packed=True, blocks=blocks,
                    interpret=self.interpret, acc_dtype=acc,
                    requant=requant)
            return lambda: ops.quant_grouped_matmul(
                xg, wg, scale, blocks=blocks, interpret=self.interpret,
                acc_dtype=acc, requant=requant)

        if sig.family == "depthwise":
            # k = kH·kW taps, n = channels; a (T, 1) kernel over a
            # (1, C, m+T-1, 1) input yields exactly m output rows — the
            # bucketed workload size — with stride 1 and no padding.
            taps, c = max(1, k), n
            x = rng.randn(1, c, m + taps - 1, 1).astype(np.float32)
            if int_requant:
                x = np.round(x * 8.0)
            w_taps = rng.randint(-7, 8, size=(taps, c)).astype(np.int8)
            if int_requant:
                scale = np.ones((c,), np.int32)
            else:
                scale = np.ones((c,), np.float32)
            return lambda: ops.quant_depthwise_conv2d(
                x, w_taps, scale, kernel_shape=(taps, 1), block=blocks,
                interpret=self.interpret, acc_dtype=acc, requant=requant)

        if sig.family == "qdq":
            x = rng.randn(m, n).astype(np.float32)
            return lambda: ops.quant_dequant(
                x, 0.05, 0.0, bit_width=sig.bits or 8, block=blocks,
                interpret=self.interpret)

        raise ValueError(sig.family)
