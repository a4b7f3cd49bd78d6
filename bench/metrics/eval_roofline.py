"""Share of the eval kernels' roofline: the least time the chip could
take for the window's evaluation work over the Mosaic kernels' device
time. The least time is the larger of the node-row applications (each
active node of each tree applied to each real row, counted on the host
from the genomes) over the bf16 peak, and one read of X and y per
generation over the HBM bandwidth. Work and time are per chip."""


def least_time_s(node_row_apps, data_bytes, peaks, chips=1):
    """(seconds, bounding term) of the least time on one of `chips`."""
    compute = node_row_apps / chips / peaks["bf16_flops_per_s"]
    memory = data_bytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def read(ctx):
    t, work = ctx["trace"], ctx["work"]
    if t is None or "node_row_apps" not in work or not ctx["peaks"]:
        return None
    kernel_s = t.kind_mean_s("mosaic")
    if kernel_s <= 0:
        return None
    least, _ = least_time_s(work["node_row_apps"], work["data_bytes"],
                            ctx["peaks"], ctx["chips"])
    return 100.0 * least / kernel_s
