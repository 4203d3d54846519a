"""Compiled QonnxGraph executor: fused segments over the Pallas kernels.

``executor.execute`` is the paper's §V oracle — node-by-node Python
dispatch, "not meant to provide high performance".  This module is the
performance tier above it (the FINN-R / Jain-et-al. compiler approach):

  1. **Partition** a cleaned graph into fused segments by iterating the
     declarative lowering-rule registry (``core/lowering``) in priority
     order.  The built-in rules cover:

     * ``Quant|BipolarQuant|QCDQ(w) -> MatMul/Gemm [-> Mul] [-> Add]`` —
       onto ``kernels.quant_matmul`` (int8) / ``quant_matmul_int4``
       (packed sub-nibble weights) with *offline* integer weight packing;
     * ``Quant|BipolarQuant|QCDQ(w) -> Conv [-> Relu] [-> Quant]`` —
       onto the same integer matmul kernels via compile-time im2col weight
       reshaping (block-diagonal for grouped/depthwise) and trace-time
       patch extraction (``kernels.quant_conv2d``);
     * activation ``Quant`` nodes and ``QuantizeLinear -> Clip ->
       DequantizeLinear`` chains — onto the fused ``kernels.quant_dequant``
       elementwise kernel;
     * everything else falls back to the interpreted op registry, traced
       into the same computation.

  2. **Emit one jitted plan function** over (consts, inputs) pytrees —
     per-node Python dispatch disappears from the hot path; weights travel
     as jit arguments (not baked literals) so the plan retraces only on new
     input shapes.

Kernel selection is **analysis-driven** (repro.analysis): the integer
range analysis proves what the *actual* weight values and activation
ranges are, so

  * a weight tensor whose values fit int4 takes the packed int4 path even
    when its declared bit width is larger;
  * weights whose declared width exceeds 8 bits still lower when their
    values fit the int8 carrier;
  * the accumulator dtype per fused matmul/conv is chosen from the
    worst-case dot-product bound (zero-padding-aware for Conv) via the
    per-rule ``GraphAnalysis.kernel_accumulator`` hook — int32 exact
    integer accumulation when the activations are provably integer-valued
    and the bound fits 31 bits, fp32 otherwise.

Pass ``use_analysis=False`` to fall back to the older syntactic
(declared-bit-width) matching.  The interpreted engine remains the
bit-exactness oracle: parity is enforced by tests/test_compile.py across
the model zoo in all three formats.
"""
from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import default_registry

from . import lowering
from .executor import lookup_op
from .graph import Node, QonnxGraph
from .lowering import LoweringContext, LoweringRule, Segment  # noqa: F401

# operand positions whose *values* must be concrete at trace time (the op
# implementations call int()/np.asarray on them); such initializers are
# closed over as numpy constants instead of travelling through the jitted
# consts pytree, where they would arrive as tracers
_STATIC_OPERANDS = {"Reshape": (1,), "Pad": (1, 2), "Squeeze": (1,),
                    "Unsqueeze": (1,)}


@dataclass
class CompiledPlan:
    """A partitioned, jit-compiled QonnxGraph execution plan.

    **Device placement** (both optional, mutually exclusive):

    * ``mesh`` — a JAX mesh: the plan becomes an SPMD program via
      ``shard_map`` over the mesh's data axes.  Weights (the consts pytree)
      are replicated across the mesh once at build; each call shards the
      slot batch's leading dim data-parallel (``dist.sharding.batch_pspecs``
      / ``to_shardings``), zero-padding non-divisible batches and slicing
      the pad back off the outputs.  Per-sample compute is untouched, so a
      sharded plan is bit-identical to the single-device plan.  A mesh
      whose data degree is 1 (e.g. ``dist.fault.elastic_mesh()`` on a
      1-device host) degenerates to the plain single-device jit path.
    * ``device`` — a single ``jax.Device``: consts and every call's inputs
      are pinned there (the per-device-worker mode ``serve.splitmerge``
      uses to spread engines over local devices).
    """
    graph: QonnxGraph
    segments: list[Segment]
    consts: dict
    analysis: Optional[object] = None      # GraphAnalysis used for selection
    tune_mode: str = "off"                 # "off" | "cached" | "search"
    tune_stats: dict = field(default_factory=dict)   # Autotuner.stats copy
    fusion: Optional[object] = None        # lowering.FusionPlan (carriers)
    mesh: Optional[object] = None          # jax Mesh — SPMD data parallelism
    device: Optional[object] = None        # jax Device — single-device pin
    _jitted: Callable = field(default=None, repr=False)

    def __post_init__(self):
        segments = self.segments
        output_names = list(self.graph.output_names)
        trace_cell = [0]
        # process-wide retrace telemetry: one counter child per model, so a
        # serving fleet's "which plan keeps retracing?" is a snapshot away
        m_retrace = default_registry().counter(
            "compile_plan_retraces_total",
            help="plan body traces (once per new input shape under jit)",
            labels={"model": self.graph.name})

        # each segment's ops carry its scope in their HLO op_name (compile-
        # time metadata only), so a device trace's ops join to segments
        scopes = [f"seg{i:02d}_{seg.kind}" for i, seg in enumerate(segments)]

        def plan(consts, inputs):
            trace_cell[0] += 1
            m_retrace.inc()
            env = dict(inputs)
            for scope, seg in zip(scopes, segments):
                with jax.named_scope(scope):
                    seg.run(consts, env)
            # graph outputs may be compile-time constants (folded subgraphs)
            return {name: env.get(name, consts.get(name))
                    for name in output_names}

        self._trace_cell = trace_cell
        self._plan = plan
        self._jitted = jax.jit(plan)
        self._jitted_donated = None        # built lazily on first donate call
        self._init_placement(plan, output_names)

    def _init_placement(self, plan, output_names) -> None:
        """Stage the mesh-SPMD / pinned-device execution paths (if any)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self.mesh is not None and self.device is not None:
            raise ValueError("pass at most one of mesh= / device=")
        self._jitted_spmd = None
        self._data_size = 1
        if self.mesh is None:
            if self.device is not None:
                self.consts = jax.device_put(self.consts, self.device)
            return
        from repro.dist import sharding as dsh
        axes = dsh._data_axes(self.mesh)
        self._data_size = dsh.data_axis_size(self.mesh)
        # weights replicated across the whole mesh once at build — per-call
        # dispatch never re-transfers them (per-group weight sharding for
        # grouped conv is a later extension; see ROADMAP)
        self.consts = jax.device_put(
            self.consts, NamedSharding(self.mesh, P()))
        if self._data_size <= 1:
            return                      # degenerate 1-device mesh: plain jit
        const_outputs = [n for n in output_names if n in self.consts]
        if const_outputs:
            # a fully-folded (constant) graph output is replicated inside
            # the body; sharding it along the batch dim would be wrong
            import logging
            logging.getLogger("repro.compile").warning(
                "plan %s has constant graph outputs %s; mesh sharding "
                "disabled, running single-device", self.graph.name,
                const_outputs)
            return
        self._batch_spec = P(axes if len(axes) > 1 else axes[0])
        # shard_map (not GSPMD auto-partitioning): each device traces the
        # plan body on its *local* batch shard with concrete local shapes,
        # so the Pallas kernel calls inside segments stay single-device
        # programs — no reliance on the SPMD partitioner understanding a
        # custom call.  Data-parallel with replicated weights needs no
        # cross-device collectives in the body (check_vma is off because
        # the body closes over per-segment kernel partials).
        spmd = jax.shard_map(plan, mesh=self.mesh,
                             in_specs=(P(), self._batch_spec),
                             out_specs=self._batch_spec, check_vma=False)
        self._jitted_spmd = jax.jit(spmd)

    @property
    def n_devices(self) -> int:
        """Devices a plan call actually spans (1 unless mesh-sharded)."""
        return self._data_size if self._jitted_spmd is not None else 1

    def placement(self) -> dict:
        """Telemetry: how the plan is placed on the host's devices."""
        if self._jitted_spmd is not None:
            return {"kind": "mesh", "devices": self._data_size,
                    "mesh": dict(self.mesh.shape)}
        if self.device is not None:
            return {"kind": "device", "devices": 1,
                    "device": str(self.device)}
        return {"kind": "host", "devices": 1}

    def _call_sharded(self, inputs: dict) -> dict:
        """Mesh path: pad the batch to a shardable multiple, place shards
        via the dist-tier sharding rules, run SPMD, slice the pad off."""
        from repro.dist import sharding as dsh
        batch = int(inputs[self.graph.input_names[0]].shape[0])
        pad = (-batch) % self._data_size
        if pad:
            inputs = {k: jnp.concatenate(
                [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
                for k, v in inputs.items()}
        inputs = jax.device_put(
            inputs, dsh.to_shardings(dsh.batch_pspecs(inputs, self.mesh),
                                     self.mesh))
        out = self._jitted_spmd(self.consts, inputs)
        if pad:
            out = {k: v[:batch]
                   if getattr(v, "ndim", 0) and v.shape[0] == batch + pad
                   else v for k, v in out.items()}
        return out

    @property
    def trace_count(self) -> int:
        """Times the plan body has executed in Python.

        Under jit that is once per new input shape — the no-retrace probe
        the serving tests assert on (a slot-padded engine must hold this
        constant across ad-hoc batch sizes).  ``jit=False`` calls and
        ``eval_shape`` traces also count, one each.
        """
        return self._trace_cell[0]

    def __call__(self, inputs: dict, *, jit: bool = True,
                 donate: bool = False) -> dict:
        """Run the plan.  Results are returned **un-forced**: under JAX's
        async dispatch they are device arrays whose compute may still be in
        flight — call ``jax.block_until_ready``/``np.asarray`` when the
        values are needed.  This is what lets the serving tier enqueue
        every slot-shaped call before a single trailing sync.

        ``donate=True`` hands the ``inputs`` buffers to XLA for reuse
        (consts are never donated).  Only honored on accelerator backends —
        CPU has no donation support, so the flag is ignored there — and the
        caller must not touch the donated buffers afterwards.  A
        mesh-sharded plan ignores donation too: the padded/resharded batch
        is a fresh buffer already.
        """
        inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
        for t in self.graph.inputs:
            if t.name not in inputs:
                raise ValueError(f"missing graph input {t.name!r}")
        if not jit:
            return self._plan(self.consts, inputs)
        if self._jitted_spmd is not None:
            return self._call_sharded(inputs)
        if self.device is not None:
            inputs = jax.device_put(inputs, self.device)
        if donate and jax.default_backend() in ("gpu", "tpu"):
            if self._jitted_donated is None:
                self._jitted_donated = jax.jit(self._plan, donate_argnums=(1,))
            return self._jitted_donated(self.consts, inputs)
        return self._jitted(self.consts, inputs)

    # ------------------------------------------------------------- stats
    @property
    def fused_counts(self) -> dict:
        out: dict[str, int] = {}
        for s in self.segments:
            out[s.kind] = out.get(s.kind, 0) + 1
        return out

    @property
    def patch_layouts(self) -> dict:
        """Patch build (``meta["patch_layout"]``) -> number of conv
        segments that unfold through it; depthwise taps have none."""
        return dict(Counter(s.meta["patch_layout"] for s in self.segments
                            if "patch_layout" in s.meta))

    @property
    def n_fused_nodes(self) -> int:
        return sum(len(s.nodes) for s in self.segments if s.kind != "interp")

    def interp_op_counts(self) -> dict:
        """op_type -> count over nodes left on the interpreted fallback."""
        out: dict[str, int] = {}
        for s in self.segments:
            if s.kind != "interp":
                continue
            for n in s.nodes:
                out[n.op_type] = out.get(n.op_type, 0) + 1
        return out

    def requant_stats(self) -> dict:
        """Integer-requant path telemetry aggregated over kernel segments.

        Only kernel-family segments (matmul/conv kinds) count — a
        ``quant_dequant`` segment quantizes from the unbounded fp32 input
        domain and is elementwise-identical to the oracle either way, so it
        has no requant path to pick.  ``coverage`` is the integer-path
        fraction (1.0 when there are no kernel segments at all);
        ``fp32_ops_eliminated`` sums each int32 segment's per-trace count
        of fp32 epilogue ops replaced by integer arithmetic.
        """
        out = {"kernel_segments": 0, "int32_segments": 0, "fp32_segments": 0,
               "fp32_ops_eliminated": 0}
        for s in self.segments:
            path = s.meta.get("requant_path")
            if path is None:
                continue
            out["kernel_segments"] += 1
            if path == "int32":
                out["int32_segments"] += 1
                out["fp32_ops_eliminated"] += s.meta.get(
                    "fp32_ops_eliminated", 0)
            else:
                out["fp32_segments"] += 1
        out["coverage"] = (out["int32_segments"] / out["kernel_segments"]
                          if out["kernel_segments"] else 1.0)
        return out

    def grouped_conv_stats(self) -> dict:
        """Grouped/depthwise-lowering telemetry aggregated over segments.

        ``reclaimed_macs`` / ``carrier_bytes_saved`` — what the dedicated
        grouped/depthwise kernels saved vs the dense block-diagonal im2col
        fallback (per inference sample);  ``grouped_segments`` — segments on
        those kernels;  ``block_diagonal_grouped`` — group>1 convs that
        still ride the dense carrier (the fallback path; 0 on the Table III
        models is the bench_compile ``--check-grouped`` gate).
        """
        out = {"grouped_segments": 0, "block_diagonal_grouped": 0,
               "reclaimed_macs": 0, "carrier_bytes_saved": 0}
        for s in self.segments:
            if s.kind in ("quant_conv", "quant_conv_int4") and \
                    s.meta.get("group", 1) > 1:
                out["block_diagonal_grouped"] += 1
            if s.kind.startswith(("quant_conv_grouped", "quant_conv_dw")):
                out["grouped_segments"] += 1
                out["reclaimed_macs"] += s.meta.get("reclaimed_macs", 0)
                out["carrier_bytes_saved"] += s.meta.get(
                    "carrier_bytes_saved", 0)
        return out

    def fusion_stats(self) -> dict:
        """Cross-segment fusion telemetry (lowering/fusion.py).

        ``fused_boundary_segments`` counts segments participating in a
        fused boundary (the four fusion-rule kinds plus kernel segments
        that produce/consume an integer carrier);
        ``integer_boundaries`` / ``packed_boundaries`` count inter-segment
        tensors travelling as int8 codes / int4-nibble-packed bytes;
        ``boundary_bytes_saved`` is the per-call HBM boundary traffic
        avoided vs the old always-fp32 boundaries; ``offers`` /
        ``declined`` expose how negotiation went (a declined offer keeps
        the exact fp32 boundary the plan had before this pass).
        ``residual_joins`` counts the ``eltwise_add`` segments by the
        carrier of their inputs (``int4x2`` / ``int8`` / ``fp32``, the
        wider where the two differ); ``skip_bytes_per_sample`` sums, per
        sample, the boundary bytes of every join input produced two or
        more segments earlier: the skip tensors a residual plan keeps
        alive across other segments.
        """
        fp = self.fusion
        out = {"enabled": fp is not None,
               "fused_boundary_segments": sum(
                   1 for s in self.segments
                   if s.meta.get("fused_boundary")),
               "integer_boundaries": 0, "packed_boundaries": 0,
               "boundary_bytes_saved": 0, "offers": 0, "declined": 0}
        if fp is not None:
            out["integer_boundaries"] = len(fp.carriers)
            out["packed_boundaries"] = sum(
                1 for c in fp.carriers.values() if c.packed)
            out["boundary_bytes_saved"] = fp.bytes_saved
            out["offers"] = fp.offered
            out["declined"] = fp.declined
        out.update(self._residual_stats())
        return out

    def _residual_stats(self) -> dict:
        widths = ("int4x2", "int8", "fp32")
        joins = dict.fromkeys(widths, 0)
        skip = 0
        producer = {t: i for i, s in enumerate(self.segments)
                    for t in s.outputs}
        for j, seg in enumerate(self.segments):
            if seg.kind != "eltwise_add":
                continue
            kinds = []
            for t in seg.inputs:
                c = self.fusion.carrier(t) if self.fusion else None
                kinds.append("fp32" if c is None else
                             "int4x2" if c.packed else "int8")
                if j - producer.get(t, -1) >= 2:
                    per_sample = 1        # symbolic dims priced as 1
                    for d in (self.graph.get_shape(t) or ())[1:]:
                        per_sample *= 1 if d is None else int(d)
                    skip += int(per_sample * (4.0 if c is None
                                              else c.bytes_per_elem))
            joins[max(kinds, key=widths.index)] += 1
        return {"residual_joins": joins, "skip_bytes_per_sample": skip}

    def tuning_stats(self) -> dict:
        """Tuned-vs-default tiling telemetry aggregated over segments.

        ``kernel_segments`` counts every segment that carries a block
        assignment (``meta["blocks"]``); ``tuned_segments`` are those whose
        blocks came from the cache or a search rather than the module
        defaults.  The cache counters (hits / misses / searched /
        graph_hit / graph_miss) are the Autotuner's, snapshotted at
        compile time — ``searched == 0`` with ``graph_hit == 1`` is the
        warm-cache invariant ``bench_compile --check-tune`` gates on.
        """
        out = {"mode": self.tune_mode, "kernel_segments": 0,
               "tuned_segments": 0, "default_segments": 0}
        for s in self.segments:
            if "blocks" not in s.meta:
                continue
            out["kernel_segments"] += 1
            if s.meta.get("tuned") in ("cached", "search"):
                out["tuned_segments"] += 1
            else:
                out["default_segments"] += 1
        out.update(self.tune_stats)
        return out

    def describe(self) -> str:
        head = (f"CompiledPlan({self.graph.name}): {len(self.segments)} "
                f"segments over {len(self.graph.nodes)} nodes "
                f"{self.fused_counts}")
        return "\n".join([head] + ["  " + s.describe() for s in self.segments])


# --------------------------------------------------- interpreted fallback

def _make_interp_segment(nodes: list[Node], static_consts: dict) -> Segment:
    fns = [lookup_op(n) for n in nodes]
    ins = sorted({i for n in nodes for i in n.inputs if i})
    outs = [o for n in nodes for o in n.outputs]

    def run(consts, env):
        for node, fn in zip(nodes, fns):
            static_pos = _STATIC_OPERANDS.get(node.op_type, ())
            args = []
            for pos, i in enumerate(node.inputs):
                if not i:
                    args.append(None)
                elif pos in static_pos and i in static_consts:
                    args.append(static_consts[i])     # concrete, not traced
                else:
                    args.append(env.get(i, consts.get(i)))
            out = fn(node, *args)
            if not isinstance(out, tuple):
                out = (out,)
            for name, val in zip(node.outputs, out):
                env[name] = val

    return Segment("interp", nodes, ins, outs, run)


# ------------------------------------------------------------- compiler

def compile_graph(graph: QonnxGraph, *, run_cleanup: bool = True,
                  use_kernels: bool = True, use_int4: bool = True,
                  use_analysis: bool = True,
                  interpret: Optional[bool] = None,
                  use_integer_requant: bool = True, tune: str = "off",
                  tune_cache_dir: Optional[str] = None,
                  tune_repeats: int = 3,
                  use_fusion: bool = True,
                  mesh=None, device=None) -> CompiledPlan:
    """Partition ``graph`` into fused segments and emit one jitted plan.

    run_cleanup  — run the declarative "compile_prep" pipeline first
                   (cleanup that keeps weight-quant nodes unfolded; shape
                   inference is what lets the channelwise matchers fire)
    use_kernels  — False disables fusion entirely (pure jitted interpreter;
                   the useful baseline for benchmarks)
    use_int4     — pack <=4-bit signed weights two-per-byte and dispatch
                   the in-kernel-unpack variant
    use_analysis — consult repro.analysis range/datatype inference for
                   kernel-variant and accumulator-dtype selection (actual
                   value ranges) instead of declared-bit-width matching
    interpret    — forwarded to the Pallas kernels; None = backend default
                   (interpreter on CPU, compiled Mosaic on GPU/TPU)
    use_integer_requant — allow the dyadic integer-epilogue fast path
                   (lowering/requant.py) on segments whose exactness proof
                   holds; False pins every segment to the fp32 epilogue
                   (the benchmark baseline for the epilogue speedup)
    tune         — per-segment kernel tilings (repro.tune):
                   "off" keeps the module-default blocks; "cached" answers
                   from the on-disk tune cache (defaults on miss, never
                   times anything); "search" additionally measures unseen
                   workloads and persists the winners
    tune_cache_dir — tune-cache root (default ``$REPRO_TUNE_CACHE_DIR`` or
                   ``<checkout>/.cache/tune``)
    tune_repeats — best-of-N repeats per candidate in "search" mode
    use_fusion   — cross-segment fusion (lowering/fusion.py): lower
                   residual Add/pool/concat/bipolar boundary ops into fused
                   segments and negotiate integer (int8 / packed-int4)
                   inter-segment carriers; False restores the pre-fusion
                   fp32-boundary plans (the regression baseline)
    mesh         — device placement: a JAX mesh (the plan runs SPMD
                   data-parallel over the mesh's data axes, weights
                   replicated — see ``CompiledPlan``), or ``"auto"`` for
                   ``dist.fault.elastic_mesh(prefer_model=1)`` (all local
                   devices data-parallel; degenerates to the single-device
                   path on a 1-device host)
    device       — pin the whole plan (consts + inputs) to one jax.Device
                   (per-device-worker serving); exclusive with ``mesh``

    Every compile records wall time and plan-shape gauges (segment counts
    per fused kind, fused-node count, integer-requant coverage, tune-cache
    hit/miss counters) into the process-wide ``repro.obs`` default
    registry under ``model=graph.name``.  On an accelerator every compile
    also turns on the JAX persistent compilation cache at its fixed
    directory (``tune.configure_jax_persistent_cache``), so a restarted
    server finds its executables again.  The CPU backend (tests,
    interpreted rehearsals) persists nothing: XLA:CPU warns on every reload
    of its own cached executables.
    """
    t_compile0 = time.perf_counter()
    from repro.kernels._blocks import resolve_interpret
    from repro.tune.cache import configure_jax_persistent_cache
    if jax.default_backend() != "cpu":
        configure_jax_persistent_cache()
    interpret = resolve_interpret(interpret)
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"mesh must be a Mesh, 'auto' or None: {mesh!r}")
        from repro.dist.fault import elastic_mesh
        mesh = elastic_mesh(prefer_model=1)   # pure data-parallel serving
    if run_cleanup:
        from . import passes
        graph = passes.run_pipeline(graph, "compile_prep")
    g = graph.copy()
    g.nodes = g.toposort()

    ga = None
    if use_kernels and use_analysis:
        from repro.analysis import analyze
        ga = analyze(g)
    tuner = None
    if use_kernels and tune != "off":
        from repro.tune import Autotuner, TuneCache, graph_cache_key
        tuner = Autotuner(TuneCache(tune_cache_dir), mode=tune,
                          repeats=tune_repeats, interpret=interpret)
        tuner.begin_graph(graph_cache_key(g, tuner.backend))
    ctx = LoweringContext(analysis=ga, use_int4=use_int4, interpret=interpret,
                          use_int_requant=use_integer_requant, tuner=tuner,
                          use_fusion=use_fusion)

    consts: dict = {k: jnp.asarray(v) for k, v in g.initializers.items()}

    # pass 1 — match the registered lowering rules at their anchor nodes.
    # Anchors are the nodes whose external inputs are all live by their
    # topo position (the MatMul/Gemm/Conv for weight-quant segments, the
    # QuantizeLinear/Quant for QDQ segments); covered satellites (weight
    # chains above, epilogues below) are recorded so pass 2 skips them.
    anchor_match: dict[int, tuple[LoweringRule, lowering.Match]] = {}
    covered: set[int] = set()
    rules_by_op: dict[str, list[LoweringRule]] = {}   # registry sorted once
    if use_kernels:
        for node in g.nodes:
            if id(node) in covered:
                continue
            if node.op_type not in rules_by_op:
                rules_by_op[node.op_type] = lowering.rules_for(node.op_type)
            for rule in rules_by_op[node.op_type]:
                m = rule.match(g, node, ctx)
                if m is None:
                    continue
                if any(id(n) in covered or id(n) in anchor_match
                       for n in m.nodes):
                    continue               # overlaps an earlier match
                anchor_match[id(node)] = (rule, m)
                covered.update(id(n) for n in m.nodes)
                break

    # carrier negotiation — after matching (it reads every match's
    # offers/accepts) and before emission (the emitters close over the
    # negotiated boundary representations): one topo walk deciding which
    # inter-segment tensors travel as integer codes instead of fp32
    fusion_plan = None
    if use_kernels and use_fusion:
        from .lowering import fusion as fusion_mod
        fusion_plan = fusion_mod.negotiate_carriers(g, anchor_match)
        ctx.fusion = fusion_plan

    # pass 1.5 — compile-time folding of the *unmatched* static subgraphs
    # (e.g. weight chains of convs no rule supports): evaluate them once
    # now so the plan never re-executes constant work per call
    folded: set[int] = set()
    changed = True
    while changed:
        changed = False
        for node in g.nodes:
            if id(node) in covered or id(node) in folded:
                continue
            if not all((not i) or i in consts for i in node.inputs):
                continue
            out = lookup_op(node)(node, *[consts[i] if i else None
                                          for i in node.inputs])
            if not isinstance(out, tuple):
                out = (out,)
            for name, val in zip(node.outputs, out):
                consts[name] = jnp.asarray(val)
            folded.add(id(node))
            changed = True

    # pass 2 — emit segments in topo order; a fused segment runs at its
    # anchor's position, consecutive unfused nodes coalesce into one
    # interpreted segment
    # initializers consumed at shape-like operand positions are closed over
    # as concrete numpy arrays (they must not arrive as jit tracers)
    static_consts = {
        i: np.asarray(consts[i])
        for node in g.nodes if node.op_type in _STATIC_OPERANDS
        for pos in _STATIC_OPERANDS[node.op_type]
        if pos < len(node.inputs) and (i := node.inputs[pos]) in consts}

    segments: list[Segment] = []
    pending_interp: list[Node] = []

    def flush_interp():
        if pending_interp:
            segments.append(
                _make_interp_segment(list(pending_interp), static_consts))
            pending_interp.clear()

    for node in g.nodes:
        if id(node) in anchor_match:
            flush_interp()
            rule, m = anchor_match[id(node)]
            segments.append(rule.emit(len(segments), m, consts, ctx))
        elif id(node) in covered or id(node) in folded:
            continue                  # satellite of a fused segment / folded
        else:
            pending_interp.append(node)
    flush_interp()

    # prune consts to what the plan actually reads: dead float weights whose
    # int8/int4 carriers were packed offline (and fold intermediates) would
    # otherwise stay resident and be flattened as jit args on every call
    used: set[str] = set()
    for seg in segments:
        used.update(seg.const_keys)
        if seg.kind == "interp":
            for node in seg.nodes:
                static_pos = _STATIC_OPERANDS.get(node.op_type, ())
                used.update(i for pos, i in enumerate(node.inputs)
                            if i and pos not in static_pos)
        else:
            used.update(seg.inputs)
    used.update(g.output_names)
    consts = {k: v for k, v in consts.items() if k in used}

    if tuner is not None:
        tuner.end_graph()
    plan = CompiledPlan(g, segments, consts, analysis=ga,
                        tune_mode=tune if tuner is not None else "off",
                        tune_stats=dict(tuner.stats) if tuner is not None
                        else {}, fusion=fusion_plan, mesh=mesh, device=device)
    _record_compile_metrics(plan, time.perf_counter() - t_compile0)
    return plan


def _record_compile_metrics(plan: CompiledPlan, wall_s: float) -> None:
    """Compile-tier telemetry into the process-wide default registry."""
    reg = default_registry()
    model = {"model": plan.graph.name}
    reg.histogram(
        "compile_wall_ms", unit="ms",
        help="compile_graph wall time (partition + analysis + plan emit)",
        window=64, labels=model).observe(wall_s * 1e3)
    reg.gauge("compile_segments",
              help="fused segments in the emitted plan, per kind",
              labels={**model, "kind": "total"}).set(len(plan.segments))
    for kind, n in plan.fused_counts.items():
        reg.gauge("compile_segments", labels={**model, "kind": kind}).set(n)
    reg.gauge("compile_fused_nodes",
              help="graph nodes absorbed into kernel segments",
              labels=model).set(plan.n_fused_nodes)
    reg.gauge("compile_plan_devices",
              help="devices a plan call spans (data-parallel degree; 1 "
                   "unless mesh-sharded)", labels=model).set(plan.n_devices)
    rq = plan.requant_stats()
    reg.gauge("compile_integer_requant_coverage",
              help="fraction of kernel segments on the integer-epilogue "
                   "fast path", labels=model).set(rq["coverage"])
    reg.gauge("compile_integer_requant_segments",
              help="kernel segments proven exact on the dyadic integer "
                   "epilogue", labels=model).set(rq["int32_segments"])
    fs = plan.fusion_stats()
    reg.gauge("compile_integer_boundaries",
              help="inter-segment tensors carried as integer codes instead "
                   "of fp32", labels=model).set(fs["integer_boundaries"])
    reg.gauge("compile_boundary_bytes_saved",
              help="per-call boundary HBM bytes avoided vs fp32 boundaries",
              labels=model).set(fs["boundary_bytes_saved"])
    for carrier, n in fs["residual_joins"].items():
        reg.gauge("compile_residual_joins",
                  help="residual eltwise_add joins, by their inputs' "
                       "carrier", labels={**model, "carrier": carrier}).set(n)
    reg.gauge("compile_skip_bytes_per_sample",
              help="per-sample bytes of join inputs kept alive across "
                   "other segments", labels=model).set(
                       fs["skip_bytes_per_sample"])
    if plan.tune_mode != "off":
        ts = plan.tuning_stats()
        reg.counter("tune_cache_hits_total",
                    help="segment tilings answered from the tune cache",
                    labels=model).inc(ts.get("hits", 0))
        reg.counter("tune_cache_misses_total",
                    help="segment tilings that fell back to defaults "
                         "(cached mode, no entry)",
                    labels=model).inc(ts.get("misses", 0))
        reg.counter("tune_searches_total",
                    help="tiling searches run (search mode, unseen "
                         "workloads)", labels=model).inc(ts.get("searched", 0))
        reg.gauge("compile_tuned_segments",
                  help="kernel segments running cache- or search-selected "
                       "tilings", labels=model).set(ts["tuned_segments"])


def execute_compiled(graph: QonnxGraph, inputs: dict, **kw) -> dict:
    """One-shot convenience: compile + run (mirrors ``executor.execute``)."""
    return compile_graph(graph, **kw)(inputs)
