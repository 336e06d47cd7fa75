"""Open-loop serving through ``DcnnServer``, one thread.

Set-up makes the served weights on the device from the seed, builds the
server (strict-VMEM Pallas primary, XLA fallback), and warms every batch
bucket the traffic can form through ``submit``/``step`` themselves.  The
window then submits each request when it is due (seeded arrivals from the
traffic file), calls ``step()`` whenever the queue holds work, and times
each request from when it was due to when ``step()`` handed its result
back.  Requests due in the window are all waited for.  A request fails if
it is shed, expires, is quarantined, or is served by anything but Pallas.

Once the window has closed and the server is freed, a seeded sample of the
requests (the last one always in it) is run through the plain f32
reference and compared with what the server returned.
"""

from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

import load
import readings as RD
import trace as T
from common import (TRACE_SECONDS, Outcome, key_for, memory_peak,
                    profile_options)


def _buckets(max_batch: int) -> list[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [max_batch]


def _padded(real: int, max_batch: int) -> int:
    b = 1
    while b < real:
        b *= 2
    return min(b, max_batch)


class _GcPauses:
    """Python's garbage collections while registered in ``gc.callbacks``:
    how many, how many of the oldest generation, and how long they held
    the loop up."""

    def __init__(self):
        self.count = self.full = 0
        self.total = self.longest = 0.0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.count += 1
            self.full += info["generation"] == 2
            self.total += dt
            self.longest = max(self.longest, dt)


def _altered(server):
    """Fault: every batch's answers altered where they are produced."""
    inner = server._dispatch

    def dispatch(*a, **k):
        y = np.array(inner(*a, **k), np.float32)
        y.reshape(y.shape[0], -1)[:, 0] += 0.5 * np.abs(y).max()
        return y
    server._dispatch = dispatch


def run(ctx) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.core.engine import EngineConfig, UniformEngine
    from repro.quant import Precision, quantize_weights
    from repro.runtime.dcnn_server import DcnnServer, ServeRequest

    cfg, tr, model = ctx.config, ctx.traffic, ctx.model
    max_batch = tr["max_batch"]
    dtype = jnp.bfloat16
    spec = model.program_served_model(cfg, key_for(ctx.seed), dtype)
    names = [l.name for l in spec.graph_for(None).layers]
    weights = jax.device_get(spec.weights)
    precision = None
    if ctx.check == "control":
        # the program's own lower precision switched on: int8 weights with
        # per-channel scales and int8 activations with per-tensor scales
        precision = Precision(weight_quant="int8", act_quant="int8")
        spec.weights = quantize_weights(spec.weights, precision)
    pallas = UniformEngine(EngineConfig(method="pallas", strict_vmem=True,
                                        max_tile_bytes=48 << 20,
                                        precision=precision))
    xla = UniformEngine(EngineConfig(method="xla", precision=precision))
    server = DcnnServer([spec], engines={"pallas": pallas, "xla": xla},
                        max_batch=max_batch, max_queue=tr["max_queue"])
    ctx.mark("weights and server")
    if ctx.fault == "altered_answer":
        _altered(server)
    shape = model.request_shape(cfg)
    pool = load.normal_pool(shape, tr["pool"], ctx.seed, dtype)
    due = load.arrivals(tr, ctx.seconds, ctx.seed)
    n = len(due)
    sample = set(load.sample_ids(n, tr["sample"], ctx.seed))

    for b in _buckets(max_batch):          # compile and warm every bucket
        for _ in range(2):
            for i in range(b):
                server.submit(ServeRequest(spec.name, pool[i % len(pool)]))
            got = server.step()
            bad = [r for r in got if not r.ok or r.engine != "pallas"]
            if len(got) != b or bad:
                raise RuntimeError(f"warm-up of batch {b}: {len(got)} "
                                   f"results, not served by Pallas: "
                                   f"{[(r.code, r.engine) for r in bad]}")
    ctx.mark("warm-up")
    qwait = server.telemetry.histogram("serve_queue_wait_seconds")
    qwait0 = (qwait.sum, qwait.count)

    lat = [None] * n
    late = []
    outputs: dict[int, np.ndarray] = {}
    failed = 0
    steps: list[tuple[int, int, bool]] = []   # (real, padded, traced)
    id_to_req: dict[int, int] = {}
    counters: dict = {}
    pauses = _GcPauses()
    with tempfile.TemporaryDirectory() as tmp:
        host = T.HostSpans()

        def stop_trace():
            # the counters cover the traced part alone: stopping the
            # profiler blocks the loop, and the backlog it leaves is not
            # the server's
            jax.profiler.stop_trace()
            host.on = False
            traced = [s for s in steps if s[2]]
            counters.update(
                queue_wait_s=qwait.sum - qwait0[0],
                queue_waits=qwait.count - qwait0[1],
                slots_real=sum(s[0] for s in traced),
                slots_padded=sum(s[1] for s in traced))

        if ctx.trace:
            host.on = True
            jax.profiler.start_trace(f"{tmp}/trace",
                                     profiler_options=profile_options())
        gc.callbacks.append(pauses)
        t0 = time.perf_counter()
        setup_s = ctx.setup_done()
        nxt = done = 0
        while done < n:
            now = time.perf_counter() - t0
            while nxt < n and due[nxt] <= now:
                with host("bench.submit"):
                    rid = server.submit(ServeRequest(
                        spec.name, pool[nxt % len(pool)]))
                id_to_req[rid] = nxt
                late.append(now - due[nxt])
                nxt += 1
            if server.queue.depth:
                with host("bench.step"):
                    results = server.step()
                t_done = time.perf_counter() - t0
                steps.append((len(results), _padded(len(results), max_batch),
                              host.on))
                for r in results:
                    j = id_to_req.pop(r.id)
                    lat[j] = t_done - due[j]
                    if not r.ok or r.engine != "pallas":
                        failed += 1
                    elif j in sample:
                        outputs[j] = np.array(r.output, np.float32)
                    done += 1
            elif nxt < n:
                time.sleep(max(0.0, due[nxt] - (time.perf_counter() - t0)))
            if host.on and time.perf_counter() - t0 >= min(TRACE_SECONDS,
                                                           ctx.seconds):
                stop_trace()
        if host.on:
            stop_trace()
        window = time.perf_counter() - t0
        gc.callbacks.remove(pauses)
        peak = memory_peak(ctx.devices)
        ctx.mark("window")
        rd = (_readings(ctx, model, cfg,
                        T.load(f"{tmp}/trace", host.kept), steps, counters)
              if ctx.trace else None)
    del server, spec, pallas, xla
    gc.collect()
    ctx.mark("trace reduced" if ctx.trace else "freed")

    checks = _check(cfg, model, weights, names, pool, sorted(sample),
                    outputs, max_batch)
    ctx.mark("reference")
    notes = [f"window: {n} requests due over {ctx.seconds} s at "
             f"{tr['rate_per_s']} req/s, {len(steps)} batches, all back "
             f"after {window:.4f} s (host clock); set-up {setup_s:.3f} s",
             f"generator lateness at submit: mean {np.mean(late) * 1e3:.4f} "
             f"ms, max {np.max(late) * 1e3:.4f} ms",
             f"garbage collection in the window: {pauses.count} passes "
             f"({pauses.full} of the oldest generation), "
             f"{pauses.total * 1e3:.4f} ms in all, longest "
             f"{pauses.longest * 1e3:.4f} ms",
             "mean latency by quarter of the window (a growing queue rises): "
             + " ".join(f"{np.mean(q) * 1e3:.3f}"
                        for q in np.array_split(np.array(lat), 4)) + " ms"]
    return Outcome({"serve_p50_ms": load.percentile(lat, 50) * 1e3,
                    "serve_p95_ms": load.percentile(lat, 95) * 1e3,
                    "setup_s": setup_s},
                   attempted=n, failed=failed,
                   checks=checks, memory_peak_bytes=peak,
                   readings=rd, notes=notes)


def _check(cfg, model, weights, names, pool, ids, outputs, block) -> dict:
    """Worst sampled request by two measures of its gap to the reference:
    ``out_rms_err``, ||served - reference|| / ||reference||, which follows
    the precision of every layer, and ``out_max_err``, max |served -
    reference| / max |reference|, which sees one element gone wrong.  A
    sampled request with no answer, or a non-finite one, reads infinity."""
    import jax
    import jax.numpy as jnp
    ref_fn = jax.jit(lambda w, x: model.reference_served(cfg, w, names, x))
    rms = mx = 0.0
    for k in range(0, len(ids), block):
        chunk = ids[k:k + block]
        x = jnp.asarray(np.stack([pool[j % len(pool)] for j in chunk]))
        ref = np.asarray(ref_fn(weights, x), np.float64)
        for j, r in zip(chunk, ref):
            got = outputs.get(j)
            if got is None or got.shape != r.shape \
                    or not np.all(np.isfinite(got)):
                return {"out_rms_err": float("inf"),
                        "out_max_err": float("inf")}
            d = got - r
            rms = max(rms, float(np.linalg.norm(d)
                                 / max(np.linalg.norm(r), 1e-30)))
            mx = max(mx, float(np.max(np.abs(d))
                               / max(np.max(np.abs(r)), 1e-30)))
    return {"out_rms_err": rms, "out_max_err": mx}


def _readings(ctx, model, cfg, tr, steps, counters):
    from flops import flops
    from peaks import PEAKS, peaks_for
    spans = [(s.start_ns, s.end_ns) for s in tr.spans
             if s.name == "bench.step"]
    traced = [s for s in steps if s[2]][:len(spans)]
    passes = [p for real, padded, _ in traced
              for p in model.serve_passes(cfg, padded)]
    per_request = sum(flops(l, 1) for l, _, _ in model.serve_passes(cfg, 1))
    useful = per_request * sum(real for real, _, _ in traced)
    lo = spans[0][0] if spans else tr.t0_ns
    hi = spans[-1][1] if spans else tr.t1_ns
    peaks = (PEAKS["TPU v5 lite"] if ctx.rehearse
             else peaks_for(ctx.devices[0].device_kind))
    return RD.Readings(kind="serve", trace=tr, lo_ns=lo, hi_ns=hi,
                       windows=spans, passes=passes, useful_flops=useful,
                       chips=ctx.chips, peaks=peaks, counters=counters)
