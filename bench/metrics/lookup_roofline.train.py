"""Embedding kernels: the bytes one lookup of the batch requires
(``bench.flops.lookup_bytes``) over the lookup's time at the chip's HBM
bandwidth, in %.  Held to the HBM bound alone: no int32 vector peak is
published for the location hashing."""


def read(ctx):
    ms = ctx.get("lookup_ms")
    if not ms:
        return None
    return 100.0 * ctx["lookup_bytes"] / (
        ms / 1e3 * ctx["peak"]["hbm_bytes_per_s"])
