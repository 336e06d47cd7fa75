#!/usr/bin/env python3
"""Compile each cell's programs at full width for a described TPU v5e,
with no chip: what the TPU compiler refuses here costs no chip time.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [--workload NAME ...]

Each serving cell compiles the server's program for every batch bucket its
traffic forms.  Prints, per program, the compile time
and ``memory_analysis()``: bytes of arguments, outputs and temporaries on
one chip.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import manifest as M  # noqa: E402


def _mib(n) -> str:
    return f"{n / 2 ** 20:.1f} MiB"


def _report(tag, fn, args) -> dict:
    import jax
    t0 = time.perf_counter()
    c = jax.jit(fn).lower(*args).compile() if not hasattr(fn, "lower") \
        else fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    ma = c.memory_analysis()
    text = c.as_text()
    row = {"program": tag, "compile_s": round(dt, 3),
           "argument": ma.argument_size_in_bytes,
           "output": ma.output_size_in_bytes,
           "temp": ma.temp_size_in_bytes,
           "pallas_calls": text.count('custom_call_target="tpu_custom_call"')}
    print(f"{tag}: compiled in {dt:.2f} s; arguments {_mib(row['argument'])}, "
          f"outputs {_mib(row['output'])}, temporaries {_mib(row['temp'])}; "
          f"{row['pallas_calls']} Pallas calls", flush=True)
    return row


def _shapes(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _engine():
    from repro.core.engine import EngineConfig, UniformEngine
    # interpret=False: the chip's lane-legal plans and Mosaic lowering
    return UniformEngine(EngineConfig(method="pallas", interpret=False,
                                      strict_vmem=True,
                                      max_tile_bytes=48 << 20))


def serve_programs(files, topo) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core.engine import compile_network
    cfg, tr, model = files["config"], files["traffic"], files["model"]
    one = SingleDeviceSharding(topo.devices[0])
    spec = model.program_served_model(cfg, jax.random.PRNGKey(0),
                                      jnp.bfloat16)
    graph = spec.graph_for(None)
    ws = _shapes(spec.weights, one)
    rows, b = [], 1
    while True:
        apply, _ = compile_network(graph, _engine(), batch=b)
        x = jax.ShapeDtypeStruct((b, *model.request_shape(cfg)),
                                 jnp.bfloat16, sharding=one)
        rows.append(_report(f"{files['cell']['name']} batch {b}", apply,
                            (ws, x)))
        if b >= tr["max_batch"]:
            return rows
        b = min(2 * b, tr["max_batch"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="*")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = M.load(BENCH.parent / "BENCHMARK.json")
    rows = []
    for w in man["workloads"]:
        if args.workload and w["name"] not in args.workload:
            continue
        files = M.files_for(man, w["name"])
        rows += serve_programs(files, topo)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
