"""Exchange on a mesh: device time of the collectives inside the
``table_lookup`` and ``table_update`` scopes on the chip that spends the
most in them, ms per window step (``bench.mesh_scopes``)."""
from bench import mesh_scopes


def read(ctx):
    got = mesh_scopes.exchange_slowest(ctx.get("table_scopes"))
    return None if got is None else got[1] * 1e3
