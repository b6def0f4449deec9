"""Events the rings dropped as a share of those recorded and dropped."""


def read(ctx):
    t = ctx["thapi"]
    total = t["events"] + t["dropped"]
    return 100.0 * t["dropped"] / total if total else None
