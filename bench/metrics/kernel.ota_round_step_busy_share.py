"""kernel.ota_round_step_busy_share: percent of the chips' busy time spent
in the ``ota_round_step`` Pallas kernel, over all chips."""
from bench import xtrace

KERNEL = "ota_round_step"


def read(ctx):
    if not ctx.planes:
        return None
    ns = sum(xtrace.kernel_ns(ctx.ops[p], KERNEL)[0] for p in ctx.planes)
    busy = sum(xtrace.busy_ns(ctx.ops[p]) for p in ctx.planes)
    if ns <= 0 or busy <= 0:
        return None
    return 100.0 * ns / busy
