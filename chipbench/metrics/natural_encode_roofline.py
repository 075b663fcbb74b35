"""The Natural encode kernel's share of its roofline: the HBM bytes the
step's encodes require (``flops.natural_encode_bytes``, every worker)
at the peak bandwidth, over the kernel's device time summed over the
chips. Bound by bytes."""
from chipbench import ef21_reference, flops
from chipbench import trace_reduce as trc

KERNEL = "natural_encode"
PHASE = "ef21/p3_ef_compress"


def read(ctx):
    frac, natural = ef21_reference.parse_compressor(ctx.traffic["w2s"])
    if not natural:
        return None
    ms = ctx.per_chip_step_ms(lambda ops: trc.kernel_ns(ops, KERNEL, PHASE))
    if not ms:
        return None
    nbytes = flops.natural_encode_bytes(ctx.specs, frac or 1.0,
                                        ctx.traffic["n_workers"])
    least, _ = flops.roofline_s(0.0, nbytes, ctx.peak)
    return 100.0 * least / (ms * ctx.n_chips / 1e3)
