"""Autotuner + persistent tune-cache tests (repro.tune).

Covers the ISSUE-8 acceptance surface:

  * cache roundtrip, content-addressed invalidation (kernel sources,
    weights, shapes, bit widths), corrupt-entry recovery, env override,
    concurrent writers (atomic last-writer-wins);
  * candidate generation invariants (VMEM feasibility, clamping, the
    default always in the timed set, max_candidates bound);
  * compile_graph(tune=...) end to end: search populates the cache and
    stamps Segment.meta["blocks"], a warm cached compile is pure hits
    with zero retunes and one jit trace, and the tuned plan stays
    bit-exact against the interpreted oracle;
  * the shared best-of-N timing harness (obs.profile) and the
    backend-derived interpret default (kernels._blocks).
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import GraphBuilder, execute
from repro.core.compile import compile_graph
from repro.tune import (Autotuner, BlockConfig, KernelSig, TuneCache,
                        bucket_rows, graph_cache_key, graph_hash,
                        kernel_version, roofline)


def _cache(tmp_path):
    """A TuneCache rooted in the test tmp dir, JAX-cache wiring off."""
    return TuneCache(str(tmp_path / "tune"))


def _mlp(seed=0, dims=(2, 12, 10, 6), w_bits=4, a_bits=4, scale=0.0973):
    """Small tie-free MLP (exact compiled-vs-oracle parity)."""
    rng = np.random.RandomState(seed)
    b = GraphBuilder("tune_mlp")
    x = b.add_input("x", (dims[0], dims[1]))
    h = x
    for i in range(1, len(dims) - 1):
        h = b.quant(h, scale, 0.0, a_bits, signed=(i == 1))
        w = b.add_initializer(
            "w", rng.randn(dims[i], dims[i + 1]).astype(np.float32) * 0.4)
        qw = b.quant(w, 0.0517, 0.0, w_bits, narrow=True)
        (h,) = b.add_node("MatMul", [h, qw], 1)
        if i < len(dims) - 2:
            (h,) = b.add_node("Relu", [h], 1)
    b.mark_output(h)
    return b.build()


# ----------------------------------------------------------- key types

def test_bucket_rows_powers_of_two():
    assert bucket_rows(None) == 1
    assert bucket_rows(0) == 1
    assert bucket_rows(1) == 1
    assert bucket_rows(2) == 2
    assert bucket_rows(3) == 4
    assert bucket_rows(64) == 64
    assert bucket_rows(900) == 1024


def test_kernel_sig_canonical_json_is_deterministic():
    a = KernelSig(family="matmul", m=64, n=32, k=16)
    b = KernelSig(family="matmul", m=64, n=32, k=16)
    assert a == b and a.canonical_json() == b.canonical_json()
    doc = json.loads(a.canonical_json())
    assert doc["family"] == "matmul" and doc["m"] == 64
    assert a.canonical_json() != KernelSig(
        family="matmul", m=64, n=32, k=16, bits=4).canonical_json()


def test_block_config_provenance():
    assert not BlockConfig(blocks=(256, 256, 512)).tuned
    assert BlockConfig(blocks=(128,), source="cached").tuned
    assert BlockConfig(blocks=(128,), source="search").tuned
    assert BlockConfig(blocks=(1, 2), source="cached").to_json() == \
        {"blocks": [1, 2], "source": "cached"}


# ----------------------------------------------------------- cache core

def test_kernel_entry_roundtrip(tmp_path):
    cache = _cache(tmp_path)
    sig = KernelSig(family="matmul", m=128, n=64, k=64)
    assert cache.lookup_kernel(sig) is None
    cache.store_kernel(sig, (128, 64, 64), best_ms=0.5, n_candidates=3)
    got = cache.lookup_kernel(sig)
    assert got == BlockConfig(blocks=(128, 64, 64), source="cached")
    # a different sig is a clean miss
    assert cache.lookup_kernel(
        KernelSig(family="matmul", m=128, n=64, k=64, bits=4)) is None


def test_manifest_roundtrip(tmp_path):
    cache = _cache(tmp_path)
    sig = KernelSig(family="qdq", m=64, n=32, k=0)
    assert cache.load_manifest("g1") is None
    cache.store_manifest("g1", {sig.canonical_json(): (64, 32)})
    assert cache.load_manifest("g1") == {sig.canonical_json(): (64, 32)}


def test_kernel_version_change_invalidates_entries(tmp_path, monkeypatch):
    cache = _cache(tmp_path)
    sig = KernelSig(family="matmul", m=128, n=64, k=64)
    cache.store_kernel(sig, (128, 64, 64))
    assert cache.lookup_kernel(sig) is not None
    # a kernel-source edit changes kernel_version() -> different entry path
    monkeypatch.setattr("repro.tune.cache.kernel_version",
                        lambda: "edited-kernels")
    assert cache.lookup_kernel(sig) is None


def test_corrupt_entries_recover_as_misses(tmp_path):
    cache = _cache(tmp_path)
    sig = KernelSig(family="matmul", m=128, n=64, k=64)
    cache.store_kernel(sig, (128, 64, 64))
    path = cache._kernel_path(sig)
    with open(path, "w") as f:
        f.write("{ not json")
    assert cache.lookup_kernel(sig) is None
    assert not os.path.exists(path)          # bad file unlinked
    cache.store_kernel(sig, (128, 64, 64))   # and storable again
    assert cache.lookup_kernel(sig) is not None
    # wrong-schema (valid JSON, bad payload) is also just a miss
    cache.store_manifest("g", {"k": (1, 2)})
    with open(cache._graph_path("g"), "w") as f:
        json.dump({"segments": "nope"}, f)
    assert cache.load_manifest("g") is None


def test_env_var_overrides_default_root(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE_DIR", str(tmp_path / "env-root"))
    cache = TuneCache()
    assert cache.root == str(tmp_path / "env-root")
    # an explicit root still wins over the env var
    cache = TuneCache(str(tmp_path / "arg-root"))
    assert cache.root == str(tmp_path / "arg-root")


def test_concurrent_writers_last_wins_whole_file(tmp_path):
    """Two processes hammering the same entry never corrupt it."""
    prog = """
import sys
from repro.tune import TuneCache, KernelSig
cache = TuneCache(sys.argv[1])
sig = KernelSig(family="matmul", m=128, n=64, k=64)
for _ in range(100):
    cache.store_kernel(sig, tuple(int(b) for b in sys.argv[2:]))
"""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    root = str(tmp_path / "tune")
    procs = [subprocess.Popen(
        [sys.executable, "-c", prog, root] + [str(b) for b in blocks],
        env=env) for blocks in [(128, 64, 64), (64, 64, 64)]]
    for p in procs:
        assert p.wait(timeout=120) == 0
    got = TuneCache(root).lookup_kernel(
        KernelSig(family="matmul", m=128, n=64, k=64))
    assert got is not None
    assert got.blocks in ((128, 64, 64), (64, 64, 64))


# ----------------------------------------------------------- graph hashing

def test_graph_hash_invalidates_on_content_changes():
    base = graph_hash(_mlp())
    assert base == graph_hash(_mlp())                       # deterministic
    assert base != graph_hash(_mlp(seed=1))                 # weights
    assert base != graph_hash(_mlp(dims=(2, 12, 14, 6)))    # shapes
    assert base != graph_hash(_mlp(w_bits=2))               # bit widths
    key = graph_cache_key(_mlp(), "cpu")
    assert key == graph_cache_key(_mlp(), "cpu")
    assert key != graph_cache_key(_mlp(), "tpu")            # backend in key


# ----------------------------------------------------------- candidates

def test_candidates_respect_vmem_and_bound(tmp_path):
    tuner = Autotuner(_cache(tmp_path), mode="cached", backend="cpu")
    sig = tuner.sig("matmul", rows=4096, n=4096, k=4096)
    cands = tuner._candidates(sig)
    assert 1 <= len(cands) <= tuner.max_candidates
    for c in cands:
        assert roofline.matmul_tile_footprint(*c) <= roofline.VMEM_BYTES
    # elementwise family: largest-resident tilings first, still bounded
    qcands = tuner._candidates(tuner.sig("qdq", rows=4096, n=4096, k=0))
    assert 1 <= len(qcands) <= tuner.max_candidates
    areas = [bm * bn for bm, bn in qcands]
    assert areas == sorted(areas, reverse=True)


def test_effective_clamps_like_the_wrappers(tmp_path):
    tuner = Autotuner(_cache(tmp_path), mode="cached", backend="cpu")
    sig = tuner.sig("matmul", rows=2, n=64, k=64)
    assert tuner._effective(sig, (256, 256, 512)) == (2, 64, 64)
    # int4 contraction blocks stay even after clamping
    sig4 = tuner.sig("matmul", rows=2, n=64, k=7, bits=4)
    assert tuner._effective(sig4, (256, 256, 512))[2] % 2 == 0
    sigd = tuner.sig("depthwise", rows=3, n=5, k=9)   # rows bucket to 4
    assert tuner._effective(sigd, (256, 128)) == (4, 5)


def test_search_times_default_and_persists(tmp_path):
    tuner = Autotuner(_cache(tmp_path), mode="search", repeats=1,
                      interpret=True, backend="cpu")
    sig = tuner.sig("qdq", rows=8, n=16, k=0)
    cfg = tuner.blocks_for(sig)
    assert cfg.source == "search"
    assert tuner.stats["searched"] == 1
    # the winner is on disk and shared: a fresh cached-mode tuner hits
    warm = Autotuner(_cache(tmp_path), mode="cached", backend="cpu")
    got = warm.blocks_for(warm.sig("qdq", rows=8, n=16, k=0))
    assert got.source == "cached" and got.blocks == cfg.blocks
    assert warm.stats == {"graph_hit": 0, "graph_miss": 0, "hits": 1,
                          "misses": 0, "searched": 0}


def test_cached_mode_empty_cache_falls_back_to_defaults(tmp_path):
    from repro.kernels.quant_matmul import DEFAULT_BLOCKS
    tuner = Autotuner(_cache(tmp_path), mode="cached", backend="cpu")
    cfg = tuner.blocks_for(tuner.sig("matmul", rows=64, n=64, k=64))
    assert cfg.source == "default" and cfg.blocks == tuple(DEFAULT_BLOCKS)
    assert tuner.stats["misses"] == 1 and tuner.stats["searched"] == 0


def test_bad_tune_mode_rejected(tmp_path):
    with pytest.raises(ValueError):
        Autotuner(_cache(tmp_path), mode="aggressive")
    with pytest.raises(ValueError):
        compile_graph(_mlp(), tune="aggressive",
                      tune_cache_dir=str(tmp_path / "t"))


# ----------------------------------------------------------- compile modes

def test_compile_search_then_cached_warm(tmp_path):
    root = str(tmp_path / "tune")
    g = _mlp()
    plan = compile_graph(g, tune="search", tune_cache_dir=root,
                         tune_repeats=1)
    st = plan.tuning_stats()
    assert st["mode"] == "search"
    assert st["kernel_segments"] >= 1
    assert st["tuned_segments"] == st["kernel_segments"]
    assert st["graph_miss"] == 1
    assert st["searched"] + st["hits"] >= st["kernel_segments"]
    for s in plan.segments:
        if "blocks" in s.meta:
            assert s.meta["tuned"] in ("cached", "search")
            assert all(isinstance(b, int) for b in s.meta["blocks"])

    # warm compile: pure cache, zero retunes, manifest answers everything
    warm = compile_graph(_mlp(), tune="cached", tune_cache_dir=root)
    wst = warm.tuning_stats()
    assert wst["mode"] == "cached"
    assert wst["graph_hit"] == 1 and wst["searched"] == 0
    assert wst["misses"] == 0
    assert wst["tuned_segments"] == wst["kernel_segments"] \
        == st["kernel_segments"]
    # and the tuned blocks agree segment-for-segment with the search plan
    assert [s.meta.get("blocks") for s in warm.segments] == \
        [s.meta.get("blocks") for s in plan.segments]


def test_compile_tune_off_stamps_nothing(tmp_path):
    plan = compile_graph(_mlp(), tune="off")
    st = plan.tuning_stats()
    assert st == {"mode": "off", "kernel_segments": 0, "tuned_segments": 0,
                  "default_segments": 0}
    assert all("blocks" not in s.meta for s in plan.segments)


def test_compile_cached_empty_cache_uses_defaults(tmp_path):
    plan = compile_graph(_mlp(), tune="cached",
                         tune_cache_dir=str(tmp_path / "empty"))
    st = plan.tuning_stats()
    assert st["kernel_segments"] >= 1
    assert st["tuned_segments"] == 0
    assert st["default_segments"] == st["kernel_segments"]
    assert st["misses"] == st["kernel_segments"]


def test_tuned_plan_exact_vs_oracle(tmp_path):
    g = _mlp()
    x = np.random.RandomState(3).randn(2, 12).astype(np.float32)
    ref = np.asarray(execute(g, {"x": x})[g.output_names[0]])
    plan = compile_graph(g, tune="search",
                         tune_cache_dir=str(tmp_path / "tune"),
                         tune_repeats=1)
    out = np.asarray(plan({"x": x})[g.output_names[0]])
    np.testing.assert_allclose(ref, out, atol=1e-5)


def test_tuned_zoo_plan_matches_oracle_and_traces_once(tmp_path):
    """TFC-w1a1 end to end: search -> warm cached -> parity + one trace."""
    from repro.models import zoo
    root = str(tmp_path / "tune")
    g = zoo.ZOO["TFC-w1a1"]()
    compile_graph(g, tune="search", tune_cache_dir=root, tune_repeats=1)
    plan = compile_graph(zoo.ZOO["TFC-w1a1"](), tune="cached",
                         tune_cache_dir=root)
    st = plan.tuning_stats()
    assert st["graph_hit"] == 1 and st["searched"] == 0
    assert st["tuned_segments"] == st["kernel_segments"] >= 1

    x = np.random.RandomState(0).randn(1, 784).astype(np.float32)
    ref = np.asarray(execute(g, {g.input_names[0]: x})[g.output_names[0]])
    out = np.asarray(plan({g.input_names[0]: x})[g.output_names[0]])
    # zoo dyadic scales admit one-quant-step tie flips (see test_compile);
    # measured bit-exact here, the envelope guards runner variance
    assert np.abs(ref - out).max() <= 3 * 0.5 + 1e-4
    assert np.array_equal(np.argmax(ref, -1), np.argmax(out, -1))
    out2 = np.asarray(plan({g.input_names[0]: x})[g.output_names[0]])
    np.testing.assert_array_equal(out, out2)
    assert plan.trace_count == 1          # same shape never retraces


# ----------------------------------------------------------- harness bits

def test_time_fn_and_time_fns_harness():
    from repro.obs.profile import time_fn, time_fns
    calls = []
    t = time_fn(lambda: calls.append(1), repeats=3, warmup=1)
    assert t >= 0.0 and len(calls) == 4              # warmup + 3 repeats
    ts = time_fns([lambda: None, lambda: None], 2)
    assert len(ts) == 2 and all(t >= 0.0 for t in ts)


def test_resolve_interpret_backend_default():
    import jax
    from repro.kernels._blocks import default_interpret, resolve_interpret
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) == default_interpret() \
        == (jax.default_backend() == "cpu")


def test_kernel_version_is_stable_hex():
    v = kernel_version()
    assert v == kernel_version()
    assert len(v) == 64 and int(v, 16) >= 0


def test_configure_jax_persistent_cache_is_latched(tmp_path):
    from repro.tune import configure_jax_persistent_cache
    first = configure_jax_persistent_cache(str(tmp_path / "jax"))
    assert configure_jax_persistent_cache(str(tmp_path / "other")) == first


@pytest.mark.parametrize("from_env", [False, True], ids=["fixed", "env"])
def test_jax_cache_dir_is_fixed_or_from_env(tmp_path, from_env):
    """Unset, the JAX cache lives at <checkout>/.cache/jax; a set
    JAX_COMPILATION_CACHE_DIR is used and no other directory is set.
    A fresh process each, since the configuration latches per process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = ("import jax\n"
            "from repro.tune import configure_jax_persistent_cache as c\n"
            "print(c())\nprint(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(root, ".cache", "jax")
    if from_env:
        want = str(tmp_path / "env-jax")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    proc = subprocess.run([sys.executable, "-c", prog], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want]
    assert os.path.isdir(want)


def test_jax_cache_setup_failure_is_logged(tmp_path, monkeypatch, caplog):
    from repro.tune import cache as cache_mod
    monkeypatch.setattr(cache_mod, "_jax_cache_configured", [])
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    with caplog.at_level(logging.WARNING, logger="repro.tune"):
        got = cache_mod.configure_jax_persistent_cache(str(blocker / "jax"))
    assert got is None
    assert "could not be set up" in caplog.text
