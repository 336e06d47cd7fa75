"""Paper Fig. 6: PE utilisation + throughput per benchmark network.

Three reproductions:
  (a) the analytic FPGA engine model (double-buffered compute vs DDR) —
      regenerates the >90%-utilisation claim and the DCGAN/GP-GAN layer-4
      memory bottleneck;
  (b) a *measured* valid-MAC fraction from compiled HLO: flops of the IOM
      lowering vs the OOM lowering of the same layer — the S^d-fold
      invalid-work elimination, observed on the compiled artifact;
  (c) LIVE utilisation from the telemetry spine: ``obs.measure_network``
      runs the compiled benchmark chains and reports achieved-GFLOP/s /
      roofline-peak per network — Fig. 6 rebuilt from wall clocks instead
      of this module's former ad-hoc ``cost_analysis()`` arithmetic.
"""

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import UniformEngine, networks, tiling
from repro.core.functional import deconv_nd
from repro.sharding.compat import cost_analysis_dict


def _hlo_flops(method, layer, batch=1):
    x = jax.ShapeDtypeStruct((batch, *layer.in_spatial, layer.cin),
                             jnp.float32)
    w = jax.ShapeDtypeStruct((*layer.kernel, layer.cin, layer.cout),
                             jnp.float32)
    c = jax.jit(lambda x, w: deconv_nd(x, w, layer.stride, 0,
                                       method=method)).lower(x, w).compile()
    return float(cost_analysis_dict(c).get("flops", 0.0))


def run() -> list[str]:
    rows = []
    for net in ("dcgan", "gp_gan", "3d_gan", "v_net"):
        s = tiling.network_summary(net)
        rows.append(f"fig6a_pe_utilization/{net},0,{s['pe_utilization']:.4f}")
        rows.append(f"fig6b_real_tops/{net},0,{s['real_tops']:.4f}")
        rows.append(f"fig6b_effective_tops/{net},0,{s['effective_tops']:.4f}")
        for p in tiling.model_network(net):
            if p.memory_bound:
                rows.append(f"fig6a_memory_bound/{p.layer},0,1")
    # measured HLO flops ratio (OOM / IOM) on a small layer of each rank
    small2d = networks.benchmark_layers("dcgan")[2]
    small3d = networks.benchmark_layers("3d_gan")[2]
    import dataclasses as dc
    small2d = dc.replace(small2d, cin=32, cout=16)
    small3d = dc.replace(small3d, cin=16, cout=8)
    for name, layer in (("2d", small2d), ("3d", small3d)):
        oom = _hlo_flops("oom", layer)
        iom = _hlo_flops("iom_phase", layer)
        rows.append(f"fig6_hlo_flops_oom/{name},0,{oom:.3e}")
        rows.append(f"fig6_hlo_flops_iom/{name},0,{iom:.3e}")
        rows.append(f"fig6_measured_mac_ratio/{name},0,{oom / iom:.3f}")
    # (c) live utilisation: RuntimeReport over the compiled reduced chains
    # (wall clocks + modeled valid MACs + roofline peak, per engine)
    gen = networks.deconv_stack("dcgan", 2, 4, [16, 8, 4, 3])
    vnet = networks.conv_stack("vnet", (8, 8, 8), [(1, 4), (4, 8)])
    for name, net in (("dcgan_gen", gen), ("vnet_enc", vnet)):
        for method in ("pallas", "xla"):
            rpt = obs.measure_network(net, UniformEngine(method=method),
                                      name=name)
            rows.append(f"fig6c_measured_util/{name}_{method},0,"
                        f"{100 * rpt.utilization:.4f}")
            rows.append(f"fig6c_achieved_gflops/{name}_{method},0,"
                        f"{rpt.achieved_gflops:.4f}")
    return rows
