"""Embedding kernels on a mesh: the HBM bytes the slowest chip's share of
the table lookup requires (``bench.table_cost.lookup_bytes``) over the
``table_lookup`` scope's time less its collectives', at the chip's HBM
bandwidth, in % (``bench.mesh_scopes.roofline``)."""
from bench import mesh_scopes


def read(ctx):
    return mesh_scopes.roofline(ctx, "table_lookup")
