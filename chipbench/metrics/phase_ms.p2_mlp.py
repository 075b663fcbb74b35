"""Device time per step of the operations under the program's ``lm.mlp``
scope inside ``ef21/p2_grads`` (every block's ln2, MLP (or MoE FFN)
and residual add, forward, recomputed and backward), averaged over the
chips; None where the scope never appears."""
from chipbench import subphase

PHASE = "ef21/p2_grads"
SUB = "lm.mlp"


def read(ctx):
    return subphase.read_ms(ctx, PHASE, subphase.P2, SUB)
