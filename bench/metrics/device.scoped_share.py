"""device.scoped_share: percent of the device self time of the traced
run's ops that lies in ops under one of the program's ``fl.`` name
scopes; it falls where a scope is dropped from the program."""
from bench import scopes


def read(ctx):
    got = scopes.device_scopes(ctx)
    if got is None or got["total_ns"] <= 0:
        return None
    return 100.0 * sum(got["by_scope"].values()) / got["total_ns"]
