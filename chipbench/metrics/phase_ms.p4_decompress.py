"""Device time per step of the operations under the program's
``ef.decompress`` scope inside ``ef21/p4_wire_recv`` (the server's
decompress of the received payloads into dense deltas: Natural decode
and scatter, without the fold into the aggregate), averaged over the
chips; None where the scope never appears."""
from chipbench import subphase

PHASE = "ef21/p4_wire_recv"
SUB = "ef.decompress"


def read(ctx):
    return subphase.read_ms(ctx, PHASE, subphase.P4, SUB)
