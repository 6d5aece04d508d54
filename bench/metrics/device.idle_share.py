"""device.idle_share: percent of the traced window in which no operation
ran on a chip (1 - busy / window, busy as the union of the device's op
intervals), averaged over the cell's chips; each chip's share goes to
standard error."""
from bench import xtrace


def read(ctx):
    if not ctx.planes or not ctx.window_s:
        return None
    shares = []
    for p in ctx.planes:
        share = 100.0 * (1.0 - xtrace.busy_ns(ctx.ops[p]) / 1e9
                         / ctx.window_s)
        ctx.log(f"# device.idle_share {p} {share!r}")
        shares.append(share)
    return sum(shares) / len(shares)
