"""Exchange on a mesh: the bytes a chip sends for the table lookup's and
update's strategies (``bench.table_cost.exchange_bytes``, the lookup's as
the ``table_lookup.<strategy>`` counter names it) over the slowest chip's
collective time in those scopes, at the chip's published ICI bandwidth
(``bench.ici``), in %."""
from bench import mesh_scopes


def read(ctx):
    got = mesh_scopes.exchange_slowest(ctx.get("table_scopes"))
    if got is None or got[1] <= 0:
        return None
    plane, s = got
    return 100.0 * ctx["table_bytes"]["exchange"][plane] / (
        s * ctx["ici_bytes_per_s"])
