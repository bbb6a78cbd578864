"""Whole step: the FLOPs one training example requires
(``bench.flops.train_flops_per_example``) times the traced window's
examples per second, over the chip's bf16 peak, in %."""


def read(ctx):
    rate = ctx.get("examples_per_s")
    if not rate:
        return None
    return 100.0 * ctx["flops_per_example"] * rate / ctx["peak"]["bf16_flops"]
