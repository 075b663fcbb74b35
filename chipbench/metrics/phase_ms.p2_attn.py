"""Device time per step of the operations under the program's ``lm.attn``
scope inside ``ef21/p2_grads`` (every block's ln1, attention, ``wo``
and residual add, forward, recomputed and backward), averaged over the
chips; None where the scope never appears."""
from chipbench import subphase

PHASE = "ef21/p2_grads"
SUB = "lm.attn"


def read(ctx):
    return subphase.read_ms(ctx, PHASE, subphase.P2, SUB)
