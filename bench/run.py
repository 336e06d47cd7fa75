#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Runs only on a TPU: off one it exits 3 and names the backend it found.
``--rehearse`` runs the same path on the CPU at the tiny widths of each data
file's ``rehearse`` block, with Pallas in interpret mode; its numbers are
never device numbers.  ``--check control`` or ``--check fault=<name>``
replace the timed path with the control or a planted fault, to read the
numbers that set the limits in ``bench/checks/``.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import manifest as M  # noqa: E402
from common import Context, log  # noqa: E402

CACHE_DIR = ROOT / ".bench_cache"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--check", default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="offered rate in place of the traffic file's, for "
                         "the knee sweep (bench/sweep.py)")
    return ap.parse_args(argv)


def _device(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    args = _args(argv)
    try:
        man = M.load(ROOT / "BENCHMARK.json")
        files = M.files_for(man, args.workload, args.rehearse)
    except (OSError, ValueError, KeyError) as e:
        log(f"bench: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: the program (src/repro) is not in {ROOT}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = files["cell"]
    if args.rate is not None:
        files["traffic"]["rate_per_s"] = args.rate
    if args.rehearse and cell["chips"] > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   "--xla_force_host_platform_device_count="
                                   f"{cell['chips']}")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    log(f"devices: {len(devices)} x {platform} {devices[0].device_kind}; "
        f"jax {jax.__version__}")
    if platform != "tpu" and not args.rehearse:
        log(f"bench: JAX found no TPU (backend {jax.default_backend()!r}, "
            f"platform {platform!r}); the benchmark runs only on a TPU")
        return 3
    if len(devices) < cell["chips"]:
        log(f"bench: {cell['name']} needs {cell['chips']} chips, JAX found "
            f"{len(devices)}")
        return 3
    devices = devices[:cell["chips"]]
    if not args.rehearse:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from peaks import peaks_for
        peaks_for(devices[0].device_kind)      # an unknown chip is an error
    ctx = Context(t_process=T_PROCESS, cell=cell, config=files["config"],
                  model=files["model"], traffic=files["traffic"],
                  limits=files["limits"], seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  rehearse=args.rehearse, check=args.check, devices=devices)
    out = files["driver"].run(ctx)

    limits = files["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in out.checks.items()}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    device = _device(devices)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if args.trace:
        metrics, extra = _per_layer(man, cell, out, devices)
        device.update(extra["device"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = extra["breakdown"]
    else:
        units = {e["name"]: e["unit"]
                 for e in M.end_to_end_for(man, cell["name"])}
        result["metrics"] = {k: {"value": out.end_to_end[k], "unit": u}
                             for k, u in units.items()
                             if k in out.end_to_end}
        result["device"] = device
    result["checks"] = checks
    ctx.mark("metrics")
    for note in out.notes + [ctx.phases()]:
        log(note)
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            f"{'' if c['value'] <= c['limit'] else '  FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


def _per_layer(man, cell, out, devices):
    """Run each per-layer metric's reader over the traced window."""
    rd = out.readings
    metrics = {}
    for p in M.per_layer_for(man, cell["name"]):
        value = M.metric_reader(p["name"]).read(p["name"], rd)
        if value is not None:
            metrics[p["name"]] = {"value": value, "unit": p["unit"]}
    return metrics, {"device": {"busy_s": rd.busy_s, "window_s": rd.window_s},
                     "breakdown": rd.breakdown}


if __name__ == "__main__":
    sys.exit(main())
