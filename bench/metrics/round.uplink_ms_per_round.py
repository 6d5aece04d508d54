"""round.uplink_ms_per_round: device self milliseconds a round in the
ops under the program's ``fl.uplink`` scope (the flat path's ravel,
noise draw, pad, tiling, quantization and unravel around the round-step
kernel), per chip."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_round(ctx, "fl.uplink")
