"""Table II / Fig. 6 reproduction: the engine blocking scheme and its model.

The paper maps a deconv layer onto a PE mesh blocked as
``Tm (out channels) x Tn (in channels) x Tz x Tr x Tc (spatial)``, with one
fixed configuration for all 2D benchmarks and one for all 3D benchmarks
(Table II).  We reproduce:

  * the exact Table II configurations and their PE counts,
  * an analytic FPGA performance model (compute cycles vs DDR traffic with
    double buffering) that regenerates Fig. 6 — PE utilisation > 90% on all
    four benchmarks *except* the memory-bound final layers of DCGAN/GP-GAN,
  * the mapping from (Tm, Tn, Tz, Tr, Tc) onto our TPU kernel blocking
    (block_co, block_ci, spatial tile), used by the Pallas kernel defaults.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core import networks


@dataclasses.dataclass(frozen=True)
class FpgaEngineConfig:
    """The paper's FPGA computation-engine configuration (Table II).

    (The TPU-side runtime configuration is ``repro.core.engine.EngineConfig``
    — this dataclass models the paper's fixed PE-mesh blocking.)
    """
    tm: int   # output-channel parallelism (PE groups)
    tn: int   # input-channel parallelism (PE planes per group)
    tz: int   # depth-direction PE planes (1 for 2D)
    tr: int   # PE rows
    tc: int   # PE cols
    data_width: int = 16
    freq_hz: float = 200e6
    ddr_bytes_per_s: float = 25.6e9   # VC709 dual DDR3-1866

    @property
    def total_pes(self) -> int:
        return self.tm * self.tn * self.tz * self.tr * self.tc

    @property
    def peak_macs_per_s(self) -> float:
        return self.total_pes * self.freq_hz

    @property
    def adder_tree_adders(self) -> int:
        # paper: Tm x Tc x Tz x log2(Tn) adders
        return self.tm * self.tc * self.tz * int(math.log2(max(self.tn, 2)))


# Table II, verbatim.
ENGINE_2D = FpgaEngineConfig(tm=2, tn=64, tz=1, tr=4, tc=4)
ENGINE_3D = FpgaEngineConfig(tm=2, tn=16, tz=4, tr=4, tc=4)

assert ENGINE_2D.total_pes == 2048 and ENGINE_3D.total_pes == 2048


def engine_for(rank: int) -> FpgaEngineConfig:
    return ENGINE_3D if rank == 3 else ENGINE_2D


@dataclasses.dataclass(frozen=True)
class LayerPerf:
    layer: str
    compute_s: float
    memory_s: float
    total_s: float
    pe_utilization: float        # compute-time occupancy (paper Fig. 6a)
    real_tops: float             # valid (IOM) ops / time
    effective_tops: float        # OOM-equivalent ops / time (zeros avoided)
    memory_bound: bool


def model_layer(layer: networks.UniformLayer,
                engine: FpgaEngineConfig | None = None) -> LayerPerf:
    """Double-buffered roofline model of one deconv layer on the engine.

    Compute time: IOM executes exactly ``valid_macs``; the engine retires
    ``total_pes`` MACs/cycle at the blocked efficiency (ceil effects when a
    dim does not divide its tile).
    Memory time: off-chip traffic at DDR bandwidth.  With double buffering
    the layer time is max(compute, memory) — the paper's utilisation metric
    is compute / total.
    """
    engine = engine or engine_for(layer.rank)
    # ceil-blocked MAC issue count (idle PEs when dims don't divide tiles)
    sp = layer.in_spatial
    if layer.rank == 3:
        spatial_tiles = (math.ceil(sp[0] / engine.tr) * math.ceil(sp[1] / engine.tc)
                         * math.ceil(sp[2] / engine.tz))
        chan_par = engine.tn
    else:
        spatial_tiles = math.ceil(sp[0] / engine.tr) * math.ceil(sp[1] / engine.tc)
        chan_par = engine.tn * engine.tz   # 2D: Tz planes re-used for channels
    blocks = (math.ceil(layer.cout / engine.tm) * math.ceil(layer.cin / chan_par)
              * spatial_tiles)
    macs_per_block = math.prod(layer.kernel) * (engine.tr * engine.tc *
                                                (engine.tz if layer.rank == 3 else 1))
    # each PE needs prod(K) cycles per activation it owns
    cycles = blocks * math.prod(layer.kernel)
    compute_s = cycles / engine.freq_hz
    del macs_per_block
    memory_s = layer.bytes_moved(engine.data_width) / engine.ddr_bytes_per_s
    total_s = max(compute_s, memory_s)
    util = compute_s / total_s
    return LayerPerf(
        layer=layer.name,
        compute_s=compute_s, memory_s=memory_s, total_s=total_s,
        pe_utilization=util,
        real_tops=2 * layer.valid_macs / total_s / 1e12,
        effective_tops=2 * layer.oom_macs / total_s / 1e12,
        memory_bound=memory_s > compute_s)


def model_network(name: str) -> list[LayerPerf]:
    return [model_layer(l) for l in networks.benchmark_layers(name)]


def network_summary(name: str) -> dict:
    perfs = model_network(name)
    total = sum(p.total_s for p in perfs)
    compute = sum(p.compute_s for p in perfs)
    valid = sum(l.valid_macs for l in networks.benchmark_layers(name))
    oom = sum(l.oom_macs for l in networks.benchmark_layers(name))
    return {
        "network": name,
        "pe_utilization": compute / total,
        "real_tops": 2 * valid / total / 1e12,
        "effective_tops": 2 * oom / total / 1e12,
        "memory_bound_layers": [p.layer for p in perfs if p.memory_bound],
    }


# -- Unified conv/deconv tiling planner (Pallas engine) ----------------------

# default VMEM budget the planner targets per grid step
DECONV_VMEM_BUDGET = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class DeconvTilePlan:
    """Joint (leading-dim tile, channel blocks) decision for one engine call.

    ``dtile`` rows of the (lifted) leading spatial dim are resident per grid
    step — INPUT rows for a deconv, OUTPUT rows for a forward conv (the two
    are the same quantity under the engine's conv<->deconv duality);
    ``n_dtiles`` is the grid extent of the sequential tile dimension (1 =
    the whole extent is a single resident tile).  The fused kernels serve
    every plan with ONE ``pallas_call``; adjacent tiles exchange their
    overlap-add halo in-grid (see kernels/deconv/kernel.py and
    kernels/conv/kernel.py).  ``step_vmem_bytes`` is the modeled per-step
    working set the decision was made against — benchmarks report it
    alongside timings.

    ``modeled_cost`` is the analytic per-layer cost (abstract seconds at
    the module's NOMINAL_* machine constants) the plan was scored with —
    zero for plans built before scoring, excluded from equality/hashing so
    a scored plan and its unscored twin stay the same cache key.  The
    ``repro.tune`` searcher re-scores candidates with calibrated machine
    numbers; this field records the ranking signal on the plan itself.
    """
    dtile: int
    n_dtiles: int
    block_ci: int
    block_co: int
    step_vmem_bytes: int
    vmem_budget: int
    modeled_cost: float = dataclasses.field(default=0.0, compare=False)

    @property
    def split(self) -> bool:
        return self.n_dtiles > 1

    @property
    def overflows(self) -> bool:
        """True when even the best plan exceeds its VMEM budget (the
        geometry cannot fit a grid step; ``EngineConfig(strict_vmem=True)``
        turns this into a typed ``VmemBudgetError``)."""
        return self.step_vmem_bytes > self.vmem_budget

    def describe(self) -> str:
        return (f"dtile{self.dtile}x{self.n_dtiles}"
                f"_ci{self.block_ci}_co{self.block_co}"
                f"_vmem{self.step_vmem_bytes}")


def plan_uniform_tiles(in_spatial, kernel, stride, cin, cout, *,
                       mode: str = "deconv",
                       vmem_budget: int = DECONV_VMEM_BUDGET,
                       block_ci: int | None = None,
                       block_co: int | None = None,
                       allow_split: bool = True,
                       backward: bool = False,
                       in_dtype_bytes: int = 2,
                       w_dtype_bytes: int | None = None,
                       groups: int = 1,
                       dilation=None,
                       lane_legal: bool = False) -> DeconvTilePlan:
    """Jointly pick ``(dtile, block_ci, block_co)`` against the VMEM budget.

    The SHARED planner entry for both directions of the uniform engine:
    ``mode="deconv"`` budgets the deconv forward (and, with
    ``backward=True``, its two VJP kernels); ``mode="conv"`` budgets the
    first-class strided convolution, where ``in_spatial`` is the PADDED
    conv input extent and ``cin``/``cout``/``block_ci``/``block_co`` keep
    their conv sense (ci contracted, co produced).  One VMEM byte model
    serves both: the conv kernel IS the deconv dx body, so its working set
    is ``kernels.conv.kernel.vmem_bytes`` and a conv training step
    additionally budgets the deconv-forward kernel (conv's dx) and the dw
    kernel with the channel roles swapped.

    Preference order follows the paper's blocking: keep channel parallelism
    (Tm/Tn -> MXU-wide 128-channel blocks) and shrink the spatial tile
    (Tz/Tr/Tc -> dtile) first; only when even ``dtile == 1`` exceeds the
    budget do channel blocks halve (block_co before block_ci, floor 8).
    Explicit ``block_ci``/``block_co`` pin the channel blocks, so only the
    spatial tile adapts.  ``allow_split=False`` pins ``n_dtiles == 1`` and
    reproduces the channels-only shrink of the old ``choose_blocks``.

    The planned leading extent includes ``ceil(K_d/S_d) - 1`` rows of zero
    slack so the final tile's halo carry-out is structurally zero (the
    kernels' contract); ``n_dtiles * dtile`` always covers it.

    ``groups`` blocks the channel grid PER GROUP: the default channel
    blocks come from the per-group channel counts (so a depthwise layer
    plans 1-wide ci blocks and each group's blocks independently respect
    the budget); ``dilation`` widens every kernel footprint in the byte
    model to the effective extent.

    ``w_dtype_bytes`` (default: ``in_dtype_bytes``) is the planner width
    of a weight element — 1 for int8-quantized weights, so quantized
    plans budget (and report) the genuinely smaller working set.

    ``lane_legal=True`` keeps every channel block Mosaic can lower: a
    block's lane extent must be the whole (per-group) channel dim or a
    multiple of 128, so the default ``min(C, 128)`` blocks never shrink and
    only the spatial tile adapts (the engine asks for this whenever it
    compiles its kernels for a TPU).
    """
    d_eff, step_bytes = step_byte_model(
        in_spatial, kernel, stride, mode=mode, backward=backward,
        in_dtype_bytes=in_dtype_bytes, w_dtype_bytes=w_dtype_bytes,
        dilation=dilation)
    assert cin % groups == 0 and cout % groups == 0, (cin, cout, groups)
    bci = block_ci or min(max(cin // groups, 1), 128)
    bco = block_co or min(max(cout // groups, 1), 128)

    dtile = d_eff
    if allow_split:
        while dtile > 1 and step_bytes(dtile, bci, bco) > vmem_budget:
            dtile = -(-dtile // 2)
    if block_co is None and not lane_legal:
        while step_bytes(dtile, bci, bco) > vmem_budget and bco > 8:
            bco //= 2
    if block_ci is None and not lane_legal:
        while step_bytes(dtile, bci, bco) > vmem_budget and bci > 8:
            bci //= 2
    n_dt = -(-d_eff // dtile)
    plan = DeconvTilePlan(dtile=dtile, n_dtiles=n_dt,
                          block_ci=bci, block_co=bco,
                          step_vmem_bytes=step_bytes(dtile, bci, bco),
                          vmem_budget=vmem_budget)
    return dataclasses.replace(plan, modeled_cost=modeled_cost(
        plan_cost_terms(plan, in_spatial, kernel, stride, cin, cout,
                        mode=mode, groups=groups, dilation=dilation,
                        in_dtype_bytes=in_dtype_bytes)))


def step_byte_model(in_spatial, kernel, stride, *, mode: str = "deconv",
                    backward: bool = False, in_dtype_bytes: int = 2,
                    w_dtype_bytes: int | None = None,
                    dilation=None):
    """The ONE per-grid-step VMEM byte model, shared by the first-fit
    heuristic (``plan_uniform_tiles``) and the tuner's candidate
    enumeration (``candidate_tile_plans`` / ``repro.tune``).

    Returns ``(d_eff, step_bytes)``: the planned leading extent (the
    lifted leading dim plus the halo-carry slack rows) and a callable
    ``step_bytes(dtile, block_ci, block_co) -> int`` evaluating the
    working set of one grid step — for ``backward=True`` the max over the
    forward and the two VJP kernels, exactly as the heuristic budgets it.

    ``w_dtype_bytes`` is the weight-element width (1 for int8 weights;
    ``None`` keeps the historical single-width model).  Only the FORWARD
    kernel's weight slab shrinks: the VJP kernels run on the dequantized
    f32 weights, so the backward terms keep nominal widths.
    """
    from repro.kernels.deconv import kernel as _k  # local: avoids a cycle

    if mode == "conv":
        from repro.core.engine import conv_output_shape
        from repro.kernels.conv import kernel as _ck

        out_sp = conv_output_shape(in_spatial, kernel, stride,
                                   dilation=dilation)
        d = out_sp[0]

        def step_bytes(dt, ci, co):
            bytes_ = _ck.vmem_bytes(out_sp, kernel, stride, ci, co,
                                    in_dtype_bytes, dtile=dt,
                                    dilation=dilation,
                                    w_dtype_bytes=w_dtype_bytes)
            if backward:
                # conv's dx is the deconv-forward kernel over dy and its dw
                # the deconv dw kernel — both with channel roles swapped
                # (they contract conv's Cout and produce conv's Cin).
                bytes_ = max(
                    bytes_,
                    _k.vmem_bytes(out_sp, kernel, stride, co, ci,
                                  in_dtype_bytes, dtile=dt,
                                  dilation=dilation),
                    _k.vmem_bytes_dw(out_sp, kernel, stride, co, ci,
                                     in_dtype_bytes, dtile=dt,
                                     dilation=dilation))
            return bytes_
    elif mode == "deconv":
        d = in_spatial[0]

        def step_bytes(dt, ci, co):
            bytes_ = _k.vmem_bytes(in_spatial, kernel, stride, ci, co,
                                   in_dtype_bytes, dtile=dt,
                                   dilation=dilation,
                                   w_dtype_bytes=w_dtype_bytes)
            if backward:
                bytes_ = max(bytes_, _k.vmem_bytes_bwd(
                    in_spatial, kernel, stride, ci, co, in_dtype_bytes,
                    dtile=dt, dilation=dilation))
            return bytes_
    else:
        raise ValueError(f"unknown mode {mode!r}; expected 'deconv'|'conv'")

    return d + _k.halo_depth(kernel, stride, dilation), step_bytes


# -- Analytic plan cost + the tuner's candidate space ------------------------

# Nominal machine constants behind the UNCALIBRATED ``modeled_cost`` on a
# plan: a mid-range host's dense-FMA throughput, streaming bandwidth, and
# per-grid-step / per-MXU-dispatch overheads.  Only RATIOS between plans of
# one geometry matter for the heuristic's bookkeeping; ``repro.tune``
# re-scores the same terms with calibrated numbers
# (``obs.machine_peak_gflops`` / ``obs.machine_mem_gbps``).
NOMINAL_PEAK_FLOPS = 100e9
NOMINAL_MEM_BPS = 50e9
NOMINAL_STEP_OVERHEAD_S = 1e-6
NOMINAL_DISPATCH_OVERHEAD_S = 2e-7


def plan_cost_terms(plan: DeconvTilePlan, in_spatial, kernel, stride,
                    cin: int, cout: int, *, mode: str = "deconv",
                    groups: int = 1, dilation=None,
                    in_dtype_bytes: int = 2, batch: int = 1) -> dict:
    """The raw accounting behind a plan's latency model, for one layer.

    Mirrors the engine's grid arithmetic (``_schedule_layer``): grid steps
    enumerate batch x output-channel blocks x leading-dim tiles x per-group
    input blocks; MXU dispatches are the non-empty polyphase taps per step.
    ``flops`` is the BLOCK-PADDED work the grid actually issues (ceil
    effects when a dim does not divide its tile are charged, exactly the
    idle-PE penalty of the paper's Fig. 6 model), and ``hbm_bytes`` charges
    each step its full VMEM working set — the double-buffered traffic a
    grid step streams.
    """
    from repro.kernels import common as _kcommon

    dilation = (tuple(dilation) if dilation is not None
                else (1,) * len(tuple(kernel)))
    g = groups
    ci_blocks = -(-(cin // g) // plan.block_ci)
    co_blocks = g * -(-(cout // g) // plan.block_co)
    grid_steps = batch * co_blocks * plan.n_dtiles * ci_blocks
    mxu_per_step = len(_kcommon.phase_taps(kernel, stride, dilation))
    if mode == "conv":
        from repro.core.engine import conv_output_shape  # local: cycle
        out_sp = conv_output_shape(in_spatial, kernel, stride,
                                   dilation=dilation)
        lead_elems = plan.dtile * math.prod(out_sp[1:])
    else:
        lead_elems = plan.dtile * math.prod(tuple(in_spatial)[1:])
    flops_per_step = (2 * math.prod(kernel) * lead_elems
                      * plan.block_ci * plan.block_co)
    return {
        "grid_steps": grid_steps,
        "mxu_dispatches": grid_steps * mxu_per_step,
        "flops": grid_steps * flops_per_step,
        "hbm_bytes": grid_steps * plan.step_vmem_bytes,
    }


def modeled_cost(terms: dict, *, peak_flops: float = NOMINAL_PEAK_FLOPS,
                 mem_bps: float = NOMINAL_MEM_BPS,
                 step_overhead_s: float = NOMINAL_STEP_OVERHEAD_S,
                 dispatch_overhead_s: float = NOMINAL_DISPATCH_OVERHEAD_S,
                 ) -> float:
    """Roofline-with-overheads latency (seconds) from ``plan_cost_terms``:
    max(compute, memory) under double buffering, plus the per-step grid
    dispatch and per-matmul MXU issue overheads that make over-split plans
    lose even when their roofline terms tie."""
    compute_s = terms["flops"] / peak_flops
    memory_s = terms["hbm_bytes"] / mem_bps
    return (max(compute_s, memory_s)
            + terms["grid_steps"] * step_overhead_s
            + terms["mxu_dispatches"] * dispatch_overhead_s)


def _halving_chain(start: int) -> list[int]:
    vals, v = [], max(start, 1)
    while True:
        vals.append(v)
        if v == 1:
            return vals
        v //= 2


def _block_candidates(chan_g: int) -> list[int]:
    """Legal channel-block extents for one grid dim: the heuristic's
    halving chain from ``min(chan_g, 128)`` plus the power-of-two ladder,
    restricted to block sizes that COVER the extent exactly (divisors) —
    with the single exception of the MXU-lane cap itself (``chan_g > 128``
    starts at 128, same as the heuristic), so every tuned plan's channel
    grid is at least as well-formed as the heuristic's."""
    start = min(max(chan_g, 1), 128)
    cands = set(_halving_chain(start))
    cands |= {p for p in (8, 16, 32, 64, 128) if p <= chan_g}
    return sorted(v for v in cands if chan_g % v == 0 or v == start)


def _dtile_candidates(d_eff: int, max_values: int = 32) -> list[int]:
    """Leading-dim tile extents: every value when the extent is small,
    else the ceil-halving chain (the heuristic's path) plus an even
    geometric fill up to ``max_values`` points."""
    if d_eff <= max_values:
        return list(range(1, d_eff + 1))
    vals = set()
    v = d_eff
    while v > 1:
        vals.add(v)
        v = -(-v // 2)
    vals.add(1)
    step = d_eff / max_values
    vals |= {max(1, round(step * i)) for i in range(1, max_values + 1)}
    return sorted(vals)


def candidate_tile_plans(in_spatial, kernel, stride, cin, cout, *,
                         mode: str = "deconv",
                         vmem_budget: int = DECONV_VMEM_BUDGET,
                         allow_split: bool = True,
                         backward: bool = False,
                         in_dtype_bytes: int = 2,
                         w_dtype_bytes: int | None = None,
                         groups: int = 1,
                         dilation=None) -> list[DeconvTilePlan]:
    """Enumerate the legal ``(dtile, block_ci, block_co)`` design space.

    The tuner's search space, built on the SAME ``step_byte_model`` the
    first-fit heuristic plans against — every returned plan satisfies the
    VMEM budget by construction, carries its working set and its
    ``modeled_cost`` at the nominal machine constants, and covers the
    heuristic's own choice (so search can never do worse than first-fit
    under the model).  When even the smallest point overflows the budget
    (the geometry cannot fit a grid step), the list degenerates to the
    heuristic's best-effort overflow plan, preserving
    ``plan_uniform_tiles``' behaviour.
    """
    d_eff, step_bytes = step_byte_model(
        in_spatial, kernel, stride, mode=mode, backward=backward,
        in_dtype_bytes=in_dtype_bytes, w_dtype_bytes=w_dtype_bytes,
        dilation=dilation)
    assert cin % groups == 0 and cout % groups == 0, (cin, cout, groups)
    dts = _dtile_candidates(d_eff) if allow_split else [d_eff]
    plans = []
    for dt in dts:
        n_dt = -(-d_eff // dt)
        for bci in _block_candidates(cin // groups):
            for bco in _block_candidates(cout // groups):
                sb = step_bytes(dt, bci, bco)
                if sb > vmem_budget:
                    continue
                plan = DeconvTilePlan(dtile=dt, n_dtiles=n_dt,
                                      block_ci=bci, block_co=bco,
                                      step_vmem_bytes=sb,
                                      vmem_budget=vmem_budget)
                plans.append(dataclasses.replace(
                    plan, modeled_cost=modeled_cost(plan_cost_terms(
                        plan, in_spatial, kernel, stride, cin, cout,
                        mode=mode, groups=groups, dilation=dilation,
                        in_dtype_bytes=in_dtype_bytes))))
    if not plans:
        plans = [plan_uniform_tiles(
            in_spatial, kernel, stride, cin, cout, mode=mode,
            vmem_budget=vmem_budget, allow_split=allow_split,
            backward=backward, in_dtype_bytes=in_dtype_bytes,
            w_dtype_bytes=w_dtype_bytes, groups=groups, dilation=dilation)]
    return plans


# -- TPU mapping -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TpuBlocking:
    """Pallas-kernel blocking derived from the paper's Tm/Tn/Tz/Tr/Tc roles.

    Tm -> block_co (output-channel tile), Tn -> block_ci (input-channel tile,
    the sequential-accumulation grid dim = the adder tree), Tz*Tr*Tc -> the
    spatial extent resident in VMEM per grid step.
    """
    block_ci: int
    block_co: int
    vmem_limit_bytes: int = 8 * 1024 * 1024


def tpu_blocking(layer_cin: int, layer_cout: int, in_spatial, kernel, stride,
                 acc_bytes: int = 4, vmem_budget: int = 8 * 1024 * 1024,
                 lane: int = 128) -> TpuBlocking:
    """Pick (block_ci, block_co) for a whole-input-resident grid step.

    Thin facade over the unified planner (``plan_uniform_tiles`` with the
    spatial split disabled — channels-only shrink), so there is exactly ONE
    VMEM budget model; ``acc_bytes``/``lane`` are retained for signature
    compatibility (the planner accumulates in f32 and caps blocks at the
    128-wide MXU lane).
    """
    del acc_bytes, lane  # the unified planner owns these decisions
    plan = plan_uniform_tiles(in_spatial, kernel, stride, layer_cin,
                              layer_cout, mode="deconv",
                              vmem_budget=vmem_budget, allow_split=False)
    return TpuBlocking(block_ci=plan.block_ci, block_co=plan.block_co,
                       vmem_limit_bytes=vmem_budget)
