"""Device time per step of the operations under the program's
``ef.select`` scope inside ``ef21/p3_ef_compress`` (TopK's abs,
``top_k`` and value gather), averaged over the chips; None where the
scope never appears."""
from chipbench import subphase

PHASE = "ef21/p3_ef_compress"
SUB = "ef.select"


def read(ctx):
    return subphase.read_ms(ctx, PHASE, subphase.P3, SUB)
