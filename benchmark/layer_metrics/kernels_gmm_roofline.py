"""The least time the chip could take for one step's grouped matmuls (the
floors of `moe.experts_roofline`: the routed pairs' FLOPs / peak or the nine
products' bytes / peak HBM bandwidth, whichever is larger) over
`kernels.gmm_ms`, the time of the kernels alone."""

from benchmark.layer_metrics import kernels_gmm_ms, moe_experts_roofline

META = {
    "name": "kernels.gmm_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    took = kernels_gmm_ms.read(run)
    if not took or run["peaks"] is None:
        return None
    return 100.0 * max(moe_experts_roofline._floors(run)) * 1e3 / took
