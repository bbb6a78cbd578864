"""Embedding layer: one jitted forward lookup of the cell's batch through
``EmbeddingTable.embed_fields`` (LMA locations and pool gather, every LMA
pool of the model), called alone on the chip, ms per call."""


def read(ctx):
    return ctx.get("lookup_ms")
