"""Whole step on a mesh: the model FLOPs one training example requires,
counted once (``bench.table_cost.train_flops_per_example``), times the
traced window's examples per second, over the chips' summed bf16 peak,
in %."""


def read(ctx):
    rate = ctx.get("examples_per_s")
    if not rate or "mesh_flops_per_example" not in ctx:
        return None
    return 100.0 * ctx["mesh_flops_per_example"] * rate / (
        ctx["chips"] * ctx["peak"]["bf16_flops"])
