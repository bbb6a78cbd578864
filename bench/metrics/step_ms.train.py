"""Trainer layer: the guarded jitted step (``Trainer.fit`` ->
``resilience.guard.make_step``), host clock to ``block_until_ready``,
median over the window's steps in ms."""
import statistics


def read(ctx):
    xs = ctx.get("step_s")
    return statistics.median(xs) * 1e3 if xs else None
