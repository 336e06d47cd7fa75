"""One configured engine, compiled schedules — the uniform front door.

The paper's core claim is a *uniform architecture*: one configurable
computation engine executes every conv and deconv layer of 2D and 3D DCNNs
from a per-layer schedule decided at compile time (loop tiling + mapping
fixed once, not re-derived per access).  This module is the software
analogue:

  * ``EngineConfig`` — the engine's configuration, decided ONCE: method
    (the deconv lowering; the conv lowering pairs automatically), numeric
    precision, VMEM budget, optional channel-block overrides, interpret
    mode.  No per-call tuning kwargs anywhere downstream.
  * ``UniformEngine`` — the configured engine.  ``engine.conv(x, w, stride,
    padding)`` and ``engine.deconv(x, w, stride, padding)`` run both
    directions of the fused Pallas grid (or the XLA baselines), and an
    internal geometry-keyed plan cache makes ``plan_uniform_tiles`` run
    once per (mode, shape, kernel, stride, channels) — not once per op
    invocation or jit retrace.
  * ``compile_network(layers, engine)`` — the compile-time mapping flow:
    takes a ``UniformLayer`` chain and returns (a) a jit-compatible
    callable running every layer on the engine and (b) a ``ScheduleReport``
    (per-layer tile plan, VMEM bytes, MXU dispatch count, sparsity) — the
    software analogue of the paper's Table-style per-layer mapping.

Semantics of ``engine.conv`` match ``lax.conv_general_dilated``
(channels-last, correlation convention, no kernel flip):

    y[n, o, co] = sum_{k, ci} x[n, o*S + k - lo, ci] * w[k, ci, co]

with per-dim output extent ``O = (I + lo + hi - K) // S + 1``; semantics of
``engine.deconv`` are the paper's Eq. (1) transposed convolution with an
optional border crop (see ``repro.core.functional``).

``conv_nd`` / ``deconv_nd`` (and the raw ``repro.kernels.{conv,deconv}``
ops) remain as thin compatibility wrappers over memoized default engines.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import networks as _networks
from repro.core import tiling as _tiling
from repro.kernels import common as _kcommon
from repro.quant.precision import Precision
from repro.core.functional import (
    METHODS,
    _canon,
    canon_padding,
    deconv_iom,
    deconv_iom_phase,
    deconv_oom,
    deconv_xla,
    dim_numbers,
    insertion_sparsity,
    pop_pallas_knobs,
)

CONV_METHODS = ("xla", "pallas")


class EngineError(Exception):
    """Base of the engine's typed failure surface."""


class ScheduleError(EngineError, ValueError):
    """A schedule could not be built or applied: broken layer chains,
    mismatched weight pytrees, batches that don't divide the mesh, …

    Subclasses ``ValueError`` so pre-existing callers (and tests) catching
    the old bare raises keep working; new callers — the serving tier's
    per-bucket fallback above all — catch ``ScheduleError`` and degrade
    instead of crashing.
    """


class VmemBudgetError(ScheduleError):
    """``plan_uniform_tiles`` could not fit a grid step inside the VMEM
    budget (raised only under ``EngineConfig(strict_vmem=True)``; the
    default engine keeps the historical best-effort plan and lets the
    kernel run over budget)."""

    def __init__(self, msg: str, plan: "_tiling.DeconvTilePlan" = None):
        super().__init__(msg)
        self.plan = plan

_XLA_DECONVS = {"oom": deconv_oom, "xla": deconv_xla, "iom": deconv_iom,
                "iom_phase": deconv_iom_phase}


def conv_output_shape(in_spatial, kernel, stride, padding=0, dilation=1):
    """Per-dim conv output extent ``O = (I + lo + hi - K_eff) // S + 1``
    with the dilated footprint ``K_eff = (K - 1) * dilation + 1``."""
    rank = len(in_spatial)
    kernel = _canon(kernel, rank)
    stride = _canon(stride, rank)
    dilation = _canon(1 if dilation is None else dilation, rank)
    pads = canon_padding(padding, rank)
    return tuple((i + lo + hi - ((k - 1) * d + 1)) // s + 1
                 for i, k, s, d, (lo, hi) in zip(in_spatial, kernel, stride,
                                                 dilation, pads))


def uniform_conv_method(deconv_method: str) -> str:
    """Map a deconv METHODS name onto the conv side of the engine.

    ``"pallas"`` keeps the whole network on the Pallas grid; every
    XLA-lowered deconv flavour (oom/xla/iom/iom_phase) pairs with the XLA
    conv baseline.
    """
    return "pallas" if deconv_method == "pallas" else "xla"


# ---------------------------------------------------------------------------
# Engine configuration — decided once, reused everywhere.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshPolicy:
    """How ``compile_network`` partitions a network over the engine's mesh.

    ``batch_axis`` shards the batch dim of every activation (pure data
    parallelism).  ``model_axis``, when set, additionally shards channels
    Megatron-style: a layer whose ``Cout`` divides the axis computes a
    channel shard of its output, the NEXT layer contracts its sharded
    ``Cin`` and ``psum``s the partial outputs (pairs alternate down the
    chain; a trailing channel-sharded output is ``all_gather``ed).  Layers
    whose channels do not divide the axis — or would fall below
    ``min_channel_block`` per device — stay replicated, exactly like real
    tensor-parallel deployments replicate awkward layers.
    """
    batch_axis: str = "data"
    model_axis: str | None = None
    min_channel_block: int = 8


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The uniform engine's compile-time configuration.

    ``method`` is the deconv lowering (one of ``METHODS``); the forward-conv
    lowering pairs via ``uniform_conv_method``.  ``precision`` (a
    ``repro.quant.Precision``) is the engine's numeric policy: activation
    storage dtype, int8 weight/activation quantization modes, per-channel
    dequant axis.  ``preferred_element_type`` is the legacy spelling of the
    storage dtype — still accepted, and normalized into an equivalent
    ``Precision(storage=...)`` at construction (passing BOTH raises).
    Either way ``cfg.precision`` is always a ``Precision`` after
    ``__post_init__`` and ``cfg.preferred_element_type`` always equals
    ``cfg.precision.storage``, so the two spellings hash and memoize
    identically.  Pallas accumulates f32 in-kernel regardless; the XLA
    deconv flavours default to f32 as before when unset.
    ``max_tile_bytes`` overrides the planner's per-grid-step VMEM budget;
    ``block_ci``/``block_co`` pin the channel blocks; ``interpret`` forces
    Pallas interpret mode (None = auto: True off-TPU).  ``strict_vmem``
    turns a budget overflow (the planner's best plan still exceeds the
    budget) into a typed ``VmemBudgetError`` at planning time instead of
    silently running over — the serving tier uses this to fall back
    per-bucket rather than OOM a device.

    ``mesh`` (optional) makes the engine mesh-aware: ``compile_network``
    then emits a ``shard_map``-wrapped callable partitioned per ``policy``
    (batch over the data axis; optionally Cout/Cin over the model axis),
    and its ``ScheduleReport`` carries per-device tile plans, per-device
    VMEM bytes and collective byte counts.  ``engine.conv``/``engine.deconv``
    called directly keep single-device semantics — the mesh only governs
    compiled schedules.

    ``telemetry`` (optional, a ``repro.obs.Telemetry``) makes the engine
    observable: ``plan`` records cache hit/miss counters and planning
    time, ``compile_network`` records compile time and wraps its callable
    with host-side dispatch timing (a pure pass-through under tracing —
    zero added jaxpr equations).  ``None`` (the default) keeps the engine
    telemetry-free: no registry is created, no instrument is ever
    touched.  ``Telemetry`` hashes by identity, so configs stay usable as
    memoization keys.

    ``tuned_plans`` (optional, a ``repro.tune.TunedPlanCache``) is the
    persisted autotuner output: on a plan-cache miss the engine consults
    it BEFORE the first-fit heuristic — a tuned geometry reaches its
    searched-and-measured plan with zero planner work (telemetry counts
    ``engine_plan_tuned_hits_total`` vs ``engine_plan_heuristic_total``).
    Plans whose working set exceeds THIS config's VMEM budget are ignored
    (a cache tuned at a larger budget can never over-commit a smaller
    engine).  Like ``Telemetry`` it hashes by identity.
    """
    method: str = "xla"
    preferred_element_type: Any = None
    precision: Precision | None = None
    max_tile_bytes: int | None = None
    block_ci: int | None = None
    block_co: int | None = None
    interpret: bool | None = None
    strict_vmem: bool = False
    mesh: Mesh | None = None
    policy: MeshPolicy = MeshPolicy()
    telemetry: Any = None
    tuned_plans: Any = None

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; expected one "
                             f"of {METHODS}")
        if self.preferred_element_type is not None:
            object.__setattr__(self, "preferred_element_type",
                               jnp.dtype(self.preferred_element_type))
        if self.precision is None:
            # the compat shim: every legacy config gets an equivalent
            # Precision, so EngineConfig(preferred_element_type=dt) and
            # EngineConfig(precision=Precision(storage=dt)) are THE SAME
            # config (equal, same hash, same memoized default engine)
            object.__setattr__(self, "precision",
                               Precision(storage=self.preferred_element_type))
        elif not isinstance(self.precision, Precision):
            raise ValueError(f"precision must be a repro.quant.Precision, "
                             f"got {self.precision!r}")
        elif (self.preferred_element_type is not None
                and self.preferred_element_type != self.precision.storage):
            # dataclasses.replace round-trips a normalized config with BOTH
            # fields set (and equal) — only a genuine conflict is an error
            raise ValueError(
                f"precision.storage={self.precision.storage} conflicts with "
                f"preferred_element_type={self.preferred_element_type}; "
                f"pass precision= alone (preferred_element_type is the "
                f"legacy spelling of Precision(storage=...))")
        else:
            object.__setattr__(self, "preferred_element_type",
                               self.precision.storage)
        if self.policy.model_axis == self.policy.batch_axis:
            raise ValueError(
                f"model_axis and batch_axis are both "
                f"{self.policy.batch_axis!r}: channel partials would psum "
                f"across different batch shards")
        if self.mesh is not None:
            names = self.mesh.axis_names
            if self.policy.batch_axis not in names:
                raise ValueError(
                    f"batch_axis {self.policy.batch_axis!r} not in mesh "
                    f"axes {names}")
            if (self.policy.model_axis is not None
                    and self.policy.model_axis not in names):
                raise ValueError(
                    f"model_axis {self.policy.model_axis!r} not in mesh "
                    f"axes {names}")

    @property
    def conv_method(self) -> str:
        return uniform_conv_method(self.method)

    @property
    def vmem_budget(self) -> int:
        return self.max_tile_bytes or _tiling.DECONV_VMEM_BUDGET

    @property
    def pallas_interpret(self) -> bool:
        """Whether the Pallas kernels run in interpret mode: ``interpret``
        when set, else everywhere but a TPU backend."""
        if self.interpret is not None:
            return self.interpret
        return _kcommon.default_interpret()


class UniformEngine:
    """The configured engine: both op directions + a compiled plan cache.

        engine = UniformEngine(method="pallas")      # or UniformEngine(cfg)
        y = engine.deconv(x, w, stride=2, padding=((0, 1), (0, 1)))
        h = engine.conv(y, w2, stride=2, padding=1)

    No per-call tuning kwargs: precision, VMEM budget, block overrides and
    interpret mode all live in the ``EngineConfig``.  ``plan`` memoizes
    ``repro.core.tiling.plan_uniform_tiles`` per layer geometry, so
    repeated calls (and jit retraces) of the same layer reuse one schedule
    — engines with different configs keep separate caches.
    """

    def __init__(self, config: EngineConfig | str | None = None, **overrides):
        if config is None:
            config = EngineConfig(**overrides)
        elif isinstance(config, str):
            config = EngineConfig(method=config, **overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        if not isinstance(config, EngineConfig):
            raise TypeError(f"expected EngineConfig | method name, got "
                            f"{config!r}")
        self.config = config
        self._plans: dict[tuple, _tiling.DeconvTilePlan] = {}
        # where each memo MISS got its plan from: "tuned" (the persisted
        # autotuner cache) vs "heuristic" (first-fit ran) — the driver's
        # zero-search assertion without telemetry plumbing
        self.plan_sources: dict[str, int] = {"tuned": 0, "heuristic": 0}

    def __repr__(self):
        return (f"UniformEngine({self.config!r}, "
                f"cached_plans={len(self._plans)})")

    # -- compile-time planning ---------------------------------------------

    @property
    def plan_cache(self) -> dict:
        """Read-only view of the geometry-keyed schedule cache."""
        return dict(self._plans)

    def plan(self, mode: str, in_spatial, kernel, stride, cin: int, cout: int,
             *, groups: int = 1, dilation=None, backward: bool = False,
             in_dtype_bytes: int = 2,
             w_dtype_bytes: int | None = None) -> _tiling.DeconvTilePlan:
        """The engine's ONLY path to the tile planner — geometry-memoized.

        ``mode="conv"`` expects the PADDED conv input extent (the planner's
        contract).  ``backward=True`` keys the training plan separately
        (it budgets max(fwd, dx, dw) working sets).  ``groups`` shrinks the
        per-group channel extents the blocks must cover; ``dilation``
        widens the halo/footprint budgets.  ``w_dtype_bytes`` is the weight
        element width when it differs from the activations' (int8 weights
        plan at 1 byte — roughly halving the modeled per-step working set
        at identical blocks); ``None`` keeps the historical
        weights-as-wide-as-activations model.
        """
        dilation = (tuple(dilation) if dilation is not None
                    else (1,) * len(tuple(in_spatial)))
        w_bytes = (int(in_dtype_bytes) if w_dtype_bytes is None
                   else int(w_dtype_bytes))
        key = (mode, tuple(in_spatial), tuple(kernel), tuple(stride),
               int(cin), int(cout), int(groups), dilation,
               bool(backward), int(in_dtype_bytes), w_bytes)
        plan = self._plans.get(key)
        tel = self.config.telemetry
        if plan is None:
            cfg = self.config
            t0 = time.perf_counter()
            tuned = None
            if cfg.tuned_plans is not None:
                tuned = cfg.tuned_plans.lookup(key,
                                               vmem_budget=cfg.vmem_budget)
            if tuned is not None:
                # the autotuner already searched this geometry: reuse its
                # winner, zero heuristic work
                plan = self._plans[key] = tuned
                self.plan_sources["tuned"] += 1
            else:
                plan = self._plans[key] = _tiling.plan_uniform_tiles(
                    key[1], key[2], key[3], key[4], key[5], mode=mode,
                    vmem_budget=cfg.vmem_budget, block_ci=cfg.block_ci,
                    block_co=cfg.block_co, groups=groups, dilation=dilation,
                    backward=backward, in_dtype_bytes=in_dtype_bytes,
                    w_dtype_bytes=w_bytes,
                    # Mosaic lowers only lane-legal channel blocks
                    lane_legal=(cfg.method == "pallas"
                                and not cfg.pallas_interpret))
                self.plan_sources["heuristic"] += 1
            if tel is not None:
                tel.registry.counter("engine_plan_cache_misses_total").inc()
                tel.registry.counter(
                    "engine_plan_tuned_hits_total" if tuned is not None
                    else "engine_plan_heuristic_total").inc()
                tel.registry.histogram("engine_plan_seconds").observe(
                    time.perf_counter() - t0)
        elif tel is not None:
            tel.registry.counter("engine_plan_cache_hits_total").inc()
        if self.config.strict_vmem and plan.overflows:
            raise VmemBudgetError(
                f"{mode} {tuple(in_spatial)}x{cin}->{cout}: best plan "
                f"{plan.describe()} exceeds the {plan.vmem_budget}-byte "
                f"VMEM budget", plan)
        return plan

    # -- the two op directions ---------------------------------------------

    def _act_quant(self, x: jax.Array, w_scale, precision: Precision | None):
        """Dynamic per-tensor int8 activation quantization (forward-only).

        Under ``Precision(act_quant="int8")`` a float activation is
        absmax-quantized at trace time and its scalar scale FOLDED into the
        weight dequant scale — the fused epilogue then undoes both
        quantizations in its one multiply.  Integer inputs pass through
        (already quantized upstream).  Returns ``(x, w_scale)``.
        """
        prec = precision if precision is not None else self.config.precision
        if prec.act_quant != "int8" or not jnp.issubdtype(x.dtype,
                                                          jnp.inexact):
            return x, w_scale
        from repro.quant import qint8 as _q8  # lazy: optional path
        s = _q8.absmax_scale(x)
        xq = _q8.quantize_q8(x, s)
        return xq, (s if w_scale is None else w_scale * s)

    @staticmethod
    def _dequant_host(x, w, w_scale, precision: Precision | None):
        """XLA-path numerics for quantized operands: dequantize the weights
        up front (mathematically identical to the Pallas engine's fused
        epilogue scale — the per-cout scale commutes with the contraction)
        and fake-quantize float activations when the policy asks, so both
        engine methods agree within rounding."""
        if jnp.issubdtype(w.dtype, jnp.integer):
            w = w.astype(jnp.float32)
            if w_scale is not None:
                w = w * w_scale
        elif w_scale is not None:
            w = w * w_scale.astype(w.dtype)
        if precision is not None and precision.act_quant == "int8" \
                and jnp.issubdtype(x.dtype, jnp.inexact):
            from repro.quant import qint8 as _q8  # lazy: optional path
            s = _q8.absmax_scale(x)
            x = _q8.dequantize_int8(_q8.quantize_q8(x, s), s).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.integer):
            x = x.astype(jnp.float32)
        return x, w

    def deconv(self, x: jax.Array, w: jax.Array, stride, padding=0, *,
               dilation=1, groups: int = 1, bias: jax.Array | None = None,
               activation: str = "none", alpha: float = 0.2,
               w_scale: jax.Array | None = None,
               precision: Precision | None = None) -> jax.Array:
        """Transposed convolution on the engine (Eq. (1) + border crop).

        ``groups``/``dilation`` follow the lax grouping/dilation
        conventions (``w`` is ``[*K, Cin/groups, Cout]``);
        ``bias``/``activation`` are the fused epilogue.  On the Pallas
        engine the epilogue runs inside the kernel flush; the XLA-lowered
        flavours apply it on the op output (and route grouped/dilated
        geometries through the generalized ``deconv_xla``, the only XLA
        flavour that lowers them).

        ``w_scale`` is the per-cout (or scalar) dequant scale of int8
        weights — on the Pallas engine it rides into the kernel and is
        applied inside the fused epilogue, pre-store-cast; the XLA flavours
        dequantize up front (same numerics, the scale commutes with the
        contraction).  ``precision`` overrides the config policy for this
        call (``compile_network`` threads per-layer overrides through it).
        """
        cfg = self.config
        if cfg.method == "pallas":
            from repro.kernels.deconv import ops as _dops  # lazy: kernels
            x, w_scale = self._act_quant(x, w_scale, precision)
            return _dops.deconv(x, w, stride, padding, dilation=dilation,
                                groups=groups, bias=bias,
                                activation=activation, alpha=alpha,
                                w_scale=w_scale, engine=self)
        x, w = self._dequant_host(
            x, w, w_scale,
            precision if precision is not None else cfg.precision)
        pet = (cfg.preferred_element_type
               if cfg.preferred_element_type is not None else jnp.float32)
        rank = x.ndim - 2
        dil = _kcommon.canon_dilation(dilation, rank)
        if groups == 1 and all(d == 1 for d in dil):
            y = _XLA_DECONVS[cfg.method](x, w, stride, padding,
                                         preferred_element_type=pet)
        else:
            y = deconv_xla(x, w, stride, padding, dilation=dil,
                           groups=groups, preferred_element_type=pet)
        if bias is not None or activation != "none":
            y = _kcommon.apply_epilogue(y, bias, activation, alpha)
        return y

    def conv(self, x: jax.Array, w: jax.Array, stride=1, padding=0, *,
             dilation=1, groups: int = 1, bias: jax.Array | None = None,
             activation: str = "none", alpha: float = 0.2,
             w_scale: jax.Array | None = None,
             precision: Precision | None = None) -> jax.Array:
        """Forward strided convolution on the engine (same epilogue,
        grouping/dilation and quantization conventions as ``deconv``)."""
        cfg = self.config
        if cfg.conv_method == "pallas":
            from repro.kernels.conv import ops as _cops  # lazy: kernels
            x, w_scale = self._act_quant(x, w_scale, precision)
            return _cops.conv(x, w, stride, padding, dilation=dilation,
                              groups=groups, bias=bias,
                              activation=activation, alpha=alpha,
                              w_scale=w_scale, engine=self)
        x, w = self._dequant_host(
            x, w, w_scale,
            precision if precision is not None else cfg.precision)
        rank = x.ndim - 2
        pet = cfg.preferred_element_type
        out_dtype = None
        if pet is None and jnp.issubdtype(x.dtype, jnp.inexact):
            # match the Pallas kernels' contract: accumulate in f32, emit
            # the input dtype (bf16 inputs must not accumulate in bf16)
            pet, out_dtype = jnp.float32, jnp.result_type(x, w)
        y = lax.conv_general_dilated(
            x, w, window_strides=_canon(stride, rank),
            padding=list(canon_padding(padding, rank)),
            rhs_dilation=_kcommon.canon_dilation(dilation, rank),
            feature_group_count=groups,
            dimension_numbers=dim_numbers(rank),
            preferred_element_type=pet)
        if bias is not None or activation != "none":
            # epilogue on the accumulator dtype, THEN the storage cast —
            # matching the Pallas kernels' in-flush ordering
            y = _kcommon.apply_epilogue(y, bias, activation, alpha)
        return y if out_dtype is None else y.astype(out_dtype)

    def __call__(self, layer: _networks.UniformLayer, x: jax.Array,
                 w: jax.Array, b: jax.Array | None = None, *,
                 w_scale: jax.Array | None = None) -> jax.Array:
        """Run one ``UniformLayer`` (op-dispatched, epilogue fused) on the
        engine."""
        op = self.deconv if layer.op == "deconv" else self.conv
        epi = layer.epilogue
        return op(x, w, layer.stride, layer.padding,
                  dilation=layer.dilation, groups=layer.groups, bias=b,
                  activation=epi.activation, alpha=epi.alpha,
                  w_scale=w_scale, precision=layer.precision)


# ---------------------------------------------------------------------------
# Default engines — the compatibility substrate for method-string callers.
# ---------------------------------------------------------------------------

_DEFAULT_ENGINES: dict[EngineConfig, UniformEngine] = {}


def default_engine(config: EngineConfig | None = None,
                   **overrides) -> UniformEngine:
    """Memoized engine per ``EngineConfig`` — so the compat wrappers
    (``deconv_nd``/``conv_nd`` and the raw kernel ops) share one plan cache
    per configuration instead of re-planning every call."""
    if config is None:
        config = EngineConfig(**overrides)
    elif overrides:
        config = dataclasses.replace(config, **overrides)
    engine = _DEFAULT_ENGINES.get(config)
    if engine is None:
        engine = _DEFAULT_ENGINES[config] = UniformEngine(config)
    return engine


def as_engine(engine, default_method: str = "xla") -> UniformEngine:
    """Coerce ``UniformEngine | EngineConfig | method-name | None`` to an
    engine (None -> the memoized default for ``default_method``)."""
    if engine is None:
        return default_engine(method=default_method)
    if isinstance(engine, UniformEngine):
        return engine
    if isinstance(engine, EngineConfig):
        return default_engine(engine)
    if isinstance(engine, str):
        return default_engine(method=engine)
    raise TypeError(f"expected UniformEngine | EngineConfig | method name, "
                    f"got {engine!r}")


def conv_nd(x: jax.Array, w: jax.Array, stride=1, padding=0,
            method: str = "xla", **kw) -> jax.Array:
    """Uniform 1D/2D/3D strided convolution — compat front-end.

    Thin wrapper over a memoized default engine for ``method``; new code
    should configure a ``UniformEngine`` once and call ``engine.conv``.
    x: [N, *spatial, Cin] with spatial rank 1..3; w: [*K, Cin, Cout];
    ``padding`` is a scalar, per-dim scalars, or per-dim ``(lo, hi)`` pairs.
    """
    if method not in CONV_METHODS:
        raise ValueError(f"unknown method {method!r}; expected one of "
                         f"{CONV_METHODS}")
    pet = kw.pop("preferred_element_type", None)
    knobs = pop_pallas_knobs(kw, method=method, op="conv_nd")
    if method != "pallas":
        knobs = {}      # meaningless for the XLA engine; accept and drop
    engine = default_engine(method=method, preferred_element_type=pet,
                            **knobs)
    return engine.conv(x, w, stride, padding)


# ---------------------------------------------------------------------------
# Compiled schedules — the paper's per-layer mapping tables, as data.
# ---------------------------------------------------------------------------

def _lift_geometry(layer: _networks.UniformLayer):
    """Mirror ``kernels.common.lift_3d``'s canonical-3D lifting on the
    layer GEOMETRY (the large, tileable dim leading; W innermost)."""
    sp, k, s = layer.in_spatial, layer.kernel, layer.stride
    p = layer.padding
    if layer.rank == 3:
        return sp, k, s, p
    if layer.rank == 2:
        return ((sp[0], 1, sp[1]), (k[0], 1, k[1]), (s[0], 1, s[1]),
                (p[0], (0, 0), p[1]))
    return ((1, 1, sp[0]), (1, 1, k[0]), (1, 1, s[0]),
            ((0, 0), (0, 0), p[0]))


@dataclasses.dataclass(frozen=True)
class LayerSchedule:
    """One row of the compiled schedule — the per-layer mapping decision.

    Merge nodes of a DAG schedule get rows too (``op`` is the merge kind,
    ``plan`` is None, zero grid/MXU accounting): the report then lists
    every node the compiled callable executes, in schedule order.
    """
    name: str
    op: str                            # "deconv" | "conv" | "concat" | "add"
    in_spatial: tuple[int, ...]
    out_spatial: tuple[int, ...]
    cin: int
    cout: int
    kernel: tuple[int, ...]
    stride: tuple[int, ...]
    plan: _tiling.DeconvTilePlan | None  # the engine's cached tile plan
    grid_steps: int                    # fused-grid steps for the forward
    mxu_per_step: int                  # tap-batched matmuls per grid step
    mxu_dispatches: int                # total MXU dispatches (forward)
    vmem_bytes: int                    # modeled per-step working set
    sparsity: float                    # zeros an OOM engine would read
    # mesh-aware accounting (equal to the globals on a single device): the
    # plan/grid/vmem numbers above are PER-DEVICE — computed from the local
    # channel blocks and per-device batch that one shard actually runs.
    local_cin: int = 0
    local_cout: int = 0
    collective: str | None = None      # "psum" | "all_gather" | None
    collective_bytes: int = 0          # per-device payload entering it
    groups: int = 1                    # channel groups (depthwise = cin)
    dilation: tuple[int, ...] = ()     # per-dim tap spacing
    epilogue: str = "-"                # fused epilogue ("bias+relu" | "-")
    precision: str = "f32"             # resolved Precision.describe()

    def __post_init__(self):
        if not self.local_cin:
            object.__setattr__(self, "local_cin", self.cin)
        if not self.local_cout:
            object.__setattr__(self, "local_cout", self.cout)
        if not self.dilation:
            object.__setattr__(self, "dilation",
                               (1,) * len(self.in_spatial))

    def describe(self) -> str:
        coll = (f" {self.collective}{self.collective_bytes}B"
                if self.collective else "")
        plan = self.plan.describe() if self.plan is not None else "merge"
        return (f"{self.name:<18s} {self.op:<6s} "
                f"{'x'.join(map(str, self.in_spatial)):>11s}x{self.cin:<4d}-> "
                f"{'x'.join(map(str, self.out_spatial)):>11s}x{self.cout:<4d} "
                f"g{self.groups:<3d} "
                f"d{'x'.join(map(str, self.dilation)):<5s} "
                f"ep:{self.epilogue:<10s} "
                f"pr:{self.precision:<8s} "
                f"{plan:<28s} grid{self.grid_steps:>5d} "
                f"mxu{self.mxu_dispatches:>6d} zeros{self.sparsity:.0%}"
                f"{coll}")

    def to_json(self) -> dict:
        return {
            "name": self.name, "op": self.op,
            "in_spatial": list(self.in_spatial),
            "out_spatial": list(self.out_spatial),
            "cin": self.cin, "cout": self.cout,
            "local_cin": self.local_cin, "local_cout": self.local_cout,
            "plan": (self.plan.describe() if self.plan is not None
                     else None),
            "grid_steps": self.grid_steps,
            "mxu_per_step": self.mxu_per_step,
            "mxu_dispatches": self.mxu_dispatches,
            "vmem_bytes": self.vmem_bytes,
            "sparsity": round(self.sparsity, 4),
            "collective": self.collective,
            "collective_bytes": self.collective_bytes,
            "groups": self.groups,
            "dilation": list(self.dilation),
            "epilogue": self.epilogue,
            "precision": self.precision,
        }


@dataclasses.dataclass(frozen=True)
class ScheduleReport:
    """The whole network's compiled schedule (batch-1 forward accounting).

    With a mesh-aware engine the per-layer rows are PER-DEVICE (local tile
    plans, per-device VMEM working sets, per-device grid steps at the
    per-device batch) plus the partition's collective accounting — halo
    exchange stays inside a device's VMEM carry (spatial dims are never
    partitioned across devices), so the cross-device traffic is exactly the
    channel-partition ``psum``/``all_gather`` payloads listed per layer.
    """
    engine: EngineConfig
    layers: tuple[LayerSchedule, ...]
    batch: int = 1
    data_parallel: int = 1             # batch-axis mesh extent
    model_parallel: int = 1            # model-axis mesh extent (1 = off)

    @property
    def mxu_dispatches(self) -> int:
        return sum(l.mxu_dispatches for l in self.layers)

    @property
    def grid_steps(self) -> int:
        return sum(l.grid_steps for l in self.layers)

    @property
    def peak_vmem_bytes(self) -> int:
        return max(l.vmem_bytes for l in self.layers)

    @property
    def unique_plans(self) -> int:
        return len({l.plan for l in self.layers})

    @property
    def collective_bytes(self) -> int:
        """Per-device payload bytes entering collectives, per forward."""
        return sum(l.collective_bytes for l in self.layers)

    @property
    def per_device_batch(self) -> int:
        return self.batch // self.data_parallel

    def describe(self) -> str:
        head = (f"schedule[{self.engine.method}] batch={self.batch} "
                f"layers={len(self.layers)} plans={self.unique_plans} "
                f"grid={self.grid_steps} mxu={self.mxu_dispatches} "
                f"peak_vmem={self.peak_vmem_bytes}")
        if self.data_parallel * self.model_parallel > 1:
            head += (f" mesh=dp{self.data_parallel}xmp{self.model_parallel} "
                     f"coll_bytes={self.collective_bytes}")
        return "\n".join([head] + ["  " + l.describe() for l in self.layers])

    def to_json(self) -> dict:
        return {
            "method": self.engine.method,
            "batch": self.batch,
            "layers": [l.to_json() for l in self.layers],
            "grid_steps": self.grid_steps,
            "mxu_dispatches": self.mxu_dispatches,
            "peak_vmem_bytes": self.peak_vmem_bytes,
            "unique_plans": self.unique_plans,
            "data_parallel": self.data_parallel,
            "model_parallel": self.model_parallel,
            "collective_bytes": self.collective_bytes,
        }


def _schedule_layer(layer: _networks.UniformLayer, engine: UniformEngine,
                    batch: int, *, local_cin: int | None = None,
                    local_cout: int | None = None,
                    collective: str | None = None,
                    collective_bytes: int = 0) -> LayerSchedule:
    cin = local_cin or layer.cin
    cout = local_cout or layer.cout
    g = layer.groups
    sp3, k3, s3, p3 = _lift_geometry(layer)
    dil3 = _kcommon.lift_tuple3(layer.dilation, layer.rank)
    if layer.op == "conv":
        plan_sp3 = tuple(i + lo + hi for i, (lo, hi) in zip(sp3, p3))
    else:
        plan_sp3 = sp3
    # the plan one device actually runs: local channel counts under a mesh;
    # the resolved precision policy (per-layer override, else the config's)
    # sets the operand widths the byte model charges — the SAME key the op
    # will plan with at trace time, so the report's plans stay resident
    prec = (layer.precision if layer.precision is not None
            else engine.config.precision)
    plan = engine.plan(layer.op, plan_sp3, k3, s3, cin, cout,
                       groups=g, dilation=dil3,
                       in_dtype_bytes=prec.act_bytes,
                       w_dtype_bytes=prec.weight_bytes)
    # the kernel grid enumerates ALL output-channel blocks but only the
    # PER-GROUP input blocks (each block contracts within its own group)
    ci_blocks = -(-(cin // g) // plan.block_ci)
    co_blocks = g * -(-(cout // g) // plan.block_co)
    grid_steps = batch * co_blocks * plan.n_dtiles * ci_blocks
    # per-phase tap batching: one wide matmul per NON-EMPTY output phase —
    # prod(min(S, K)) at dilation 1 (stride 1 collapses to a single
    # dispatch); dilation can leave phases structurally empty, so count
    # the actual tap table
    mxu_per_step = len(_kcommon.phase_taps(k3, s3, dil3))
    sparsity = (insertion_sparsity(layer.in_spatial, layer.kernel,
                                   layer.stride)
                if layer.op == "deconv" else 0.0)
    return LayerSchedule(
        name=layer.name, op=layer.op, in_spatial=layer.in_spatial,
        out_spatial=layer.out_spatial, cin=layer.cin, cout=layer.cout,
        kernel=layer.kernel, stride=layer.stride, plan=plan,
        grid_steps=grid_steps, mxu_per_step=mxu_per_step,
        mxu_dispatches=grid_steps * mxu_per_step,
        vmem_bytes=plan.step_vmem_bytes, sparsity=sparsity,
        local_cin=cin, local_cout=cout, collective=collective,
        collective_bytes=collective_bytes, groups=g,
        dilation=layer.dilation, epilogue=layer.epilogue.describe(),
        precision=prec.describe())


def _schedule_merge(node: _networks.MergeNode, graph: _networks.UniformGraph,
                    ) -> LayerSchedule:
    """A zero-cost schedule row for a DAG merge node — the report accounts
    every node the compiled callable executes."""
    sp, cout = graph.node_shape(node.name)
    cin = sum(graph.node_shape(p)[1] for p in graph.edges[node.name])
    return LayerSchedule(
        name=node.name, op=node.kind, in_spatial=sp, out_spatial=sp,
        cin=cin, cout=cout, kernel=(), stride=(), plan=None,
        grid_steps=0, mxu_per_step=0, mxu_dispatches=0, vmem_bytes=0,
        sparsity=0.0)


# ---------------------------------------------------------------------------
# Mesh partitioning — batch over "data", optionally Cout/Cin over "model".
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _LayerPartition:
    """One layer's placement: its weight PartitionSpec, the channel extents
    one device holds, and the collective (if any) that follows the layer."""
    w_spec: P
    local_cin: int
    local_cout: int
    collective: str | None             # "psum" | "all_gather" | None


def _partition_layers(layers, policy: MeshPolicy,
                      model_size: int) -> list[_LayerPartition]:
    """Megatron-style alternation down the chain: shard a layer's Cout when
    it divides the model axis, contract the NEXT layer's (then-sharded) Cin
    and psum its partial outputs; a trailing channel-sharded output is
    all_gathered so the compiled callable always returns full channels."""
    parts = []
    act_sharded = False
    for i, l in enumerate(layers):
        cin_l, cout_l, coll = l.cin, l.cout, None
        spec = [None] * (l.rank + 2)
        if act_sharded:
            # input channels arrive sharded: each device contracts its Cin
            # block into FULL-Cout partial sums, reduced right after
            spec[l.rank] = policy.model_axis
            cin_l = l.cin // model_size
            coll = "psum"
            act_sharded = False
        elif (model_size > 1 and l.cout % model_size == 0
              and l.cout // model_size >= policy.min_channel_block):
            spec[l.rank + 1] = policy.model_axis
            cout_l = l.cout // model_size
            act_sharded = True
            if i == len(layers) - 1:
                coll = "all_gather"
        parts.append(_LayerPartition(
            w_spec=P(*spec), local_cin=cin_l,
            local_cout=cout_l, collective=coll))
    return parts


def _collective_bytes(layer, part: _LayerPartition, per_dev_batch: int,
                      act_bytes: int) -> int:
    """Per-device payload entering the layer's collective — the same
    quantity the jaxpr's psum/all_gather operand carries."""
    if part.collective is None:
        return 0
    chans = (layer.cout if part.collective == "psum" else part.local_cout)
    return act_bytes * per_dev_batch * math.prod(layer.out_spatial) * chans


def _compile_sharded(layers, engine: UniformEngine, batch: int):
    """The mesh-aware compile path: a ``shard_map``-wrapped callable (batch
    over the data axis, channels optionally over the model axis) plus the
    per-device schedule report."""
    from repro.sharding.compat import shard_map_norep

    cfg = engine.config
    mesh, policy = cfg.mesh, cfg.policy
    dp = mesh.shape[policy.batch_axis]
    mp = mesh.shape[policy.model_axis] if policy.model_axis else 1
    if batch % dp:
        raise ScheduleError(
            f"compile batch {batch} does not divide the {dp}-way "
            f"{policy.batch_axis!r} mesh axis")
    parts = _partition_layers(layers, policy, mp)
    per_dev_batch = batch // dp
    # activation bytes entering the collectives: the configured element
    # type, else the f32 the engines default to for inexact inputs
    act_bytes = (cfg.preferred_element_type.itemsize
                 if cfg.preferred_element_type is not None else 4)
    report = ScheduleReport(
        engine=cfg, batch=batch, data_parallel=dp, model_parallel=mp,
        layers=tuple(
            _schedule_layer(l, engine, per_dev_batch,
                            local_cin=pt.local_cin, local_cout=pt.local_cout,
                            collective=pt.collective,
                            collective_bytes=_collective_bytes(
                                l, pt, per_dev_batch, act_bytes))
            for l, pt in zip(layers, parts)))

    def local_apply(ws, x):
        h = x
        for layer, w, part in zip(layers, ws, parts):
            epi = layer.epilogue
            if part.collective == "psum" and not epi.is_identity:
                # a channel-contracting layer produces PARTIAL sums: its
                # epilogue does not commute with the reduction, so defer
                # it until after the psum (host-side, same semantics)
                op = engine.deconv if layer.op == "deconv" else engine.conv
                h = op(h, w.astype(h.dtype), layer.stride, layer.padding,
                       dilation=layer.dilation, groups=layer.groups)
                h = lax.psum(h, policy.model_axis)
                h = _kcommon.apply_epilogue(h, None, epi.activation,
                                            epi.alpha)
                continue
            h = engine(layer, h, w.astype(h.dtype))
            if part.collective == "psum":
                h = lax.psum(h, policy.model_axis)
            elif part.collective == "all_gather":
                h = lax.all_gather(h, policy.model_axis, axis=h.ndim - 1,
                                   tiled=True)
        return h

    sharded = shard_map_norep(
        local_apply, mesh=mesh,
        in_specs=([pt.w_spec for pt in parts], P(policy.batch_axis)),
        out_specs=P(policy.batch_axis))

    def apply(ws, x):
        if len(ws) != len(layers):
            raise ScheduleError(f"expected {len(layers)} weight arrays, got "
                                f"{len(ws)}")
        if any(isinstance(e, dict) for e in ws):
            raise ScheduleError(
                "channel-partitioned chains take bare weight arrays; "
                "quantized {'w_q', 'scale'} entries are only supported on "
                "unsharded chains and (data-parallel) graph schedules")
        if x.shape[0] % dp:
            raise ScheduleError(
                f"batch {x.shape[0]} does not divide the {dp}-way "
                f"{policy.batch_axis!r} mesh axis")
        return sharded(list(ws), x)

    return apply, report


def _layer_wb(entry, layer: _networks.UniformLayer):
    """Split one weight pytree entry into (w, bias-or-None, scale-or-None).

    Quantized entries — ``repro.quant.quantize_weights`` output — carry
    ``{"w_q": int8, "scale": per-cout}`` (plus ``"b"`` when the epilogue
    declares a bias) and are accepted anywhere a ``{"w", "b"}`` entry is.
    """
    if isinstance(entry, dict):
        if "w_q" in entry:
            w, s = entry["w_q"], entry.get("scale")
        else:
            w, s = entry["w"], entry.get("scale")
        b = entry.get("b")
    else:
        w, b, s = entry, None, None
    if layer.epilogue.bias and b is None:
        raise ScheduleError(f"layer {layer.name!r} declares a fused bias but "
                         f"its weight entry carries none (expected "
                         f"{{'w', 'b'}})")
    return w, b, s


def _graph_report(graph: _networks.UniformGraph, engine: UniformEngine,
                  batch: int, **mesh_kw) -> ScheduleReport:
    rows = []
    for name in graph.order:
        nd = graph.nodes[name]
        rows.append(_schedule_layer(nd, engine, batch)
                    if isinstance(nd, _networks.UniformLayer)
                    else _schedule_merge(nd, graph))
    return ScheduleReport(engine=engine.config, batch=batch,
                          layers=tuple(rows), **mesh_kw)


def _graph_apply_fn(graph: _networks.UniformGraph, engine: UniformEngine):
    """The compiled DAG walk: one engine call per layer node (epilogue
    fused), one concat/add per merge node, intermediates dropped as soon
    as their last consumer has run."""
    last_use: dict[str, str] = {}
    for name in graph.order:
        for p in graph.edges[name]:
            last_use[p] = name
    layer_names = [l.name for l in graph.layers]
    # the storage-dtype contract: with no explicit preferred_element_type
    # every node emits its input's dtype (the Pallas kernels already do —
    # f32 accumulation in-kernel — and the XLA flavours' f32 outputs cast
    # back), so a bf16 graph stays bf16 END TO END with no astype in the
    # hot loop
    keep_dtype = engine.config.preferred_element_type is None

    def apply(ws, x):
        missing = [n for n in layer_names if n not in ws]
        if missing:
            raise ScheduleError(f"graph weights missing entries for {missing}")
        vals: dict[str, jax.Array] = {graph.INPUT: x}
        for name in graph.order:
            nd = graph.nodes[name]
            ins = [vals[p] for p in graph.edges[name]]
            if isinstance(nd, _networks.MergeNode):
                if nd.kind == "concat":
                    vals[name] = jnp.concatenate(ins, axis=-1)
                else:
                    out = ins[0]
                    for v in ins[1:]:
                        out = out + v
                    vals[name] = out
            else:
                w, b, s = _layer_wb(ws[name], nd)
                h = ins[0]
                # int8 weights stay int8 into the kernel (the astype that
                # keeps a bf16 graph bf16 would silently dequantize them)
                wv = (w if jnp.issubdtype(w.dtype, jnp.integer)
                      else w.astype(h.dtype))
                out = engine(nd, h, wv,
                             None if b is None else b.astype(h.dtype),
                             w_scale=s)
                vals[name] = out.astype(h.dtype) if keep_dtype else out
            for p in graph.edges[name]:
                if last_use[p] == name and p != graph.output:
                    vals.pop(p, None)
        return vals[graph.output]

    return apply


def _compile_graph(graph: _networks.UniformGraph, engine: UniformEngine,
                   batch: int):
    """DAG schedules on one device — topological walk over the nodes."""
    report = _graph_report(graph, engine, batch)
    return _graph_apply_fn(graph, engine), report


def _compile_graph_sharded(graph: _networks.UniformGraph,
                           engine: UniformEngine, batch: int):
    """The mesh-aware DAG path: pure data parallelism — the batch shards
    over the data axis, weights replicate (``P()``), and the whole DAG walk
    runs inside one ``shard_map`` region (skip tensors never cross
    devices).  Megatron-style channel sharding stays a chain-only feature:
    a DAG's merge nodes would force gathers at every skip.
    """
    from repro.sharding.compat import shard_map_norep

    cfg = engine.config
    mesh, policy = cfg.mesh, cfg.policy
    dp = mesh.shape[policy.batch_axis]
    if batch % dp:
        raise ScheduleError(
            f"compile batch {batch} does not divide the {dp}-way "
            f"{policy.batch_axis!r} mesh axis")
    # rows carry PER-DEVICE accounting (the batch one shard runs); the
    # report-level batch stays GLOBAL, matching the chain path
    report = dataclasses.replace(
        _graph_report(graph, engine, batch // dp, data_parallel=dp),
        batch=batch)
    local_apply = _graph_apply_fn(graph, engine)
    sharded = shard_map_norep(
        local_apply, mesh=mesh, in_specs=(P(), P(policy.batch_axis)),
        out_specs=P(policy.batch_axis))

    def apply(ws, x):
        if x.shape[0] % dp:
            raise ScheduleError(
                f"batch {x.shape[0]} does not divide the {dp}-way "
                f"{policy.batch_axis!r} mesh axis")
        return sharded(ws, x)

    return apply, report


def compile_network(layers: Sequence[_networks.UniformLayer]
                    | _networks.UniformGraph,
                    engine: UniformEngine | EngineConfig | str,
                    *, batch: int = 1,
                    ) -> tuple[Callable, ScheduleReport]:
    """Compile a ``UniformLayer`` chain OR a ``UniformGraph`` DAG onto one
    configured engine.

    Returns ``(apply, report)``: ``apply(ws, x)`` is a jit-compatible
    callable running every node on the engine in schedule order, and
    ``report`` is the per-node ``ScheduleReport`` — every tile plan it
    lists is resident in the engine's cache, so executing ``apply``
    (including under jit, and across retraces) never re-runs the planner.

    For a chain, ``ws`` is the per-layer weight list (each
    ``[*K, Cin/groups, Cout]``).  For a graph, ``ws`` is a dict keyed by
    layer name: a bare weight array, or ``{"w": ..., "b": ...}`` when the
    layer's epilogue declares a fused bias
    (``init_network_weights(graph, key)`` builds the matching pytree).
    Merge nodes own no weights; epilogues (bias + activation) execute
    inside the engine's kernels — a compiled graph traces ZERO elementwise
    ops outside merges.

    With a mesh-aware engine (``EngineConfig(mesh=..., policy=...)``) the
    callable is ``shard_map``-wrapped: ``apply`` still takes FULL (global)
    weights and batch — the wrapper splits them per the partition — and the
    report's rows become per-device.  Chains partition Megatron-style per
    the policy's model axis; graphs shard the batch axis only (weights
    replicated), since skip merges would otherwise gather at every node.

    A chain must be geometrically consistent (layer i's output feeds layer
    i+1); a graph validated its edges at construction.  The schedule
    accounts a batch-``batch`` forward.
    """
    engine = engine if isinstance(engine, UniformEngine) else as_engine(engine)
    tel = engine.config.telemetry
    t0 = time.perf_counter()
    if isinstance(layers, _networks.UniformGraph):
        graph = layers
        tag = f"graph:{graph.output}"
        if engine.config.mesh is not None:
            built = _compile_graph_sharded(graph, engine, batch)
        else:
            built = _compile_graph(graph, engine, batch)
    else:
        layers = tuple(layers)
        if not layers:
            raise ScheduleError("compile_network needs at least one layer")
        for prev, nxt in zip(layers, layers[1:]):
            if prev.out_spatial != nxt.in_spatial or prev.cout != nxt.cin:
                raise ScheduleError(
                    f"layer chain breaks at {prev.name} -> {nxt.name}: "
                    f"{prev.out_spatial}x{prev.cout} != "
                    f"{nxt.in_spatial}x{nxt.cin}")
        tag = f"chain:{layers[0].name}x{len(layers)}"
        if engine.config.mesh is not None:
            built = _compile_sharded(layers, engine, batch)
        else:
            chain = layers

            def chain_apply(ws, x):
                if len(ws) != len(chain):
                    raise ScheduleError(
                        f"expected {len(chain)} weight arrays, got "
                        f"{len(ws)}")
                h = x
                for layer, entry in zip(chain, ws):
                    if isinstance(entry, dict):
                        # quantized {"w_q", "scale"} (or {"w", "b"}) entries
                        # ride the chain exactly like graph entries
                        w, b, s = _layer_wb(entry, layer)
                        wv = (w if jnp.issubdtype(w.dtype, jnp.integer)
                              else w.astype(h.dtype))
                        h = engine(layer, h, wv,
                                   None if b is None else b.astype(h.dtype),
                                   w_scale=s)
                    else:
                        h = engine(layer, h, entry.astype(h.dtype))
                return h

            built = chain_apply, ScheduleReport(
                engine=engine.config, batch=batch,
                layers=tuple(_schedule_layer(l, engine, batch)
                             for l in chain))
    apply, report = built
    if tel is not None:
        from repro.obs.report import instrument_apply  # lazy: opt-in only
        dt = time.perf_counter() - t0
        tel.registry.histogram("engine_compile_seconds",
                               schedule=tag).observe(dt)
        tel.tracer.event("compile", schedule=tag,
                         method=engine.config.method, batch=batch,
                         layers=len(report.layers), duration_s=dt)
        apply = instrument_apply(apply, tel, tag)
    return apply, report


def init_network_weights(layers: Sequence[_networks.UniformLayer]
                         | _networks.UniformGraph, key,
                         dtype=jnp.float32, scale: float = 0.05):
    """Weights for a compiled network: a per-layer ``[*K, Cin/G, Cout]``
    list for a chain, or the name-keyed dict ``compile_network`` expects
    for a ``UniformGraph`` (``{"w", "b"}`` entries where the layer's
    epilogue declares a fused bias, zero-initialised biases)."""
    if isinstance(layers, _networks.UniformGraph):
        graph = layers
        ls = graph.layers
        keys = jax.random.split(key, len(ls))
        ws = {}
        for k, l in zip(keys, ls):
            w = scale * jax.random.normal(k, l.weight_shape, dtype)
            ws[l.name] = ({"w": w, "b": jnp.zeros((l.cout,), dtype)}
                          if l.epilogue.bias else w)
        return ws
    keys = jax.random.split(key, len(layers))
    return [scale * jax.random.normal(k, l.weight_shape, dtype)
            for k, l in zip(keys, layers)]
