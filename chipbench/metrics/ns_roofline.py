"""The server's Newton-Schulz kernels' share of their roofline: the
operations the step's iterations require (``flops.ns_step_flops``,
3 m^2 n + m^3 per slice and iteration: the symmetric gram and its
square need their upper triangles only) at the bf16 peak, or their
bytes at the peak bandwidth where that is longer, over the kernels'
device time summed over the chips."""
from chipbench import flops
from chipbench import trace_reduce as trc

KERNELS = ("ns_iteration_fused", "fused_matmul")
PHASE = "ef21/p5_lmo"
NS_STEPS = 5


def read(ctx):
    ms = ctx.per_chip_step_ms(
        lambda ops: sum(trc.kernel_ns(ops, k, PHASE) for k in KERNELS))
    if not ms:
        return None
    least, _ = flops.roofline_s(flops.ns_step_flops(ctx.specs, NS_STEPS),
                                flops.ns_step_bytes(ctx.specs, NS_STEPS),
                                ctx.peak)
    return 100.0 * least / (ms * ctx.n_chips / 1e3)
