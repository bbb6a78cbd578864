"""Optimizer layer: one jitted update (sparse Adagrad on the pool's
SparseGrad, dense Adagrad on the rest, applied in place) on one step's
gradients, called alone on the chip, ms per call."""


def read(ctx):
    return ctx.get("update_ms")
