"""Device time per step of the operations under the program's
``ef.update`` scope inside ``ef21/p3_ef_compress`` (the sender's own
decompress of its message and the EF21 estimate add), averaged over
the chips; None where the scope never appears."""
from chipbench import subphase

PHASE = "ef21/p3_ef_compress"
SUB = "ef.update"


def read(ctx):
    return subphase.read_ms(ctx, PHASE, subphase.P3, SUB)
