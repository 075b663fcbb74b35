"""Device time per step of the operations under the program's
``ef21/p3_ef_compress`` scope, averaged over the chips."""
from chipbench import trace_reduce as trc

PHASE = "ef21/p3_ef_compress"


def read(ctx):
    ms = ctx.per_chip_step_ms(lambda ops: trc.phase_ns(ops).get(PHASE, 0.0))
    return ms or None
