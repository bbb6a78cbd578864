"""Device: the share of the traced training window in which no operation
ran on the chip (``bench.trace.reduce``), in %."""


def read(ctx):
    r = ctx.get("trace")
    return None if r is None else 100.0 * r["idle_share"]
