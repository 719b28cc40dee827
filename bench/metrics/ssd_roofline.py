"""``ssd_chunk_kernel``'s share of its roofline in the prefills of the
traced slice: the least time its work needs at the chip's peaks (flops
and bytes from ``bench.roofline.work.ssd_chunk``, one call per Mamba2
layer per prefill) over the kernel's device time."""
from bench.roofline.peaks import peaks
from bench.roofline.work import ssd_chunk
from bench.trace import kernel_time


def read(ctx):
    a = ctx["arch"]
    k = kernel_time(ctx, "ssd")
    if k is None:
        return None
    d_in = a["ssm_expand"] * a["d_model"]
    h = d_in // a["ssm_head_dim"]
    flops = nbytes = 0.0
    for length, n in k["lengths"].items():
        s = -(-length // a["ssm_chunk"]) * a["ssm_chunk"]
        w = ssd_chunk(1, h, 1, s, a["ssm_chunk"], a["ssm_head_dim"], a["ssm_state"])
        flops += w["flops"] * n * a["n_layers"]
        nbytes += w["bytes"] * n * a["n_layers"]
    pk = peaks(ctx["kind"])
    return 100.0 * max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"]) / k["device_s"]
