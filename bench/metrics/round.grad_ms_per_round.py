"""round.grad_ms_per_round: device self milliseconds a round in the ops
under the program's ``fl.grad`` scope (minibatch draw, per-device
gradients, clip and norm), per chip."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_round(ctx, "fl.grad")
