"""Optimizer layer on a mesh: device time of the program's
``table_update`` scope (row-sparse Adagrad on every 'model'-sharded table,
its apply included) on the chip that spends the most in it, ms per window
step (``bench.mesh_scopes``)."""
from bench import mesh_scopes


def read(ctx):
    got = mesh_scopes.slowest(ctx.get("table_scopes"), "table_update")
    return None if got is None else got[1] * 1e3
