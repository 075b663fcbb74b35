"""Device time per step of the operations under the program's
``lm.vocab`` scope inside ``ef21/p2_grads`` (the token and position
lookup, the tied head and the cross-entropy, forward and backward),
averaged over the chips; None where the scope never appears."""
from chipbench import subphase

PHASE = "ef21/p2_grads"
SUB = "lm.vocab"


def read(ctx):
    return subphase.read_ms(ctx, PHASE, subphase.P2, SUB)
