"""Share of the traced window in which no operation ran on a chip,
averaged over the chips."""
from chipbench import trace_reduce as trc


def read(ctx):
    ops = ctx.device_ops()
    busy = sum(trc.busy_ns(o) for o in ops.values()) / len(ops) / 1e9
    return 100.0 * (1.0 - busy / ctx.window_s())
