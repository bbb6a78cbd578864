"""Input layer: the benchmark's own batch making and host->device put, per
window step (host spans around the trainer's ``batch_fn``), median in ms."""
import statistics


def read(ctx):
    xs = ctx.get("input_ms")
    return statistics.median(xs) if xs else None
