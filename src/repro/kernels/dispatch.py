"""The TPU dispatch rule: which Pallas engines a chip run may reach.

Every production gate that could route work to a Pallas engine asks
``pallas_allowed`` first: ``embed.backends.fused_eligible`` (the fused
lookup and the sparse-grad location kernel), the exchange gates
``dist.exchange.fused_slab_eligible`` / ``fused_chunk_eligible``, and the
sparse optimizer update (``kernels.sparse_update.ops``).  Off the TPU the
engines run in interpret mode and stay eligible — they are the bit-exact
twins the CPU tests hold to the split oracle.  On the TPU an engine the
v5e compiler refuses is never dispatched: the split/XLA path takes its
place, decided here, before tracing, and never by catching a compile error.

``TPU_REFUSED`` names each excluded engine with the compiler's own reason
(an AOT compile against a described ``v5e:2x2``, jax 0.9.0); delete an
entry once its kernels lower and ``tests/test_tpu_compile.py`` compiles
them at real widths.
"""
from __future__ import annotations

import jax

TPU_REFUSED = {
    "fused_embed": (
        "the minhash takes a min over uint32 hashes (Mosaic: 'Reductions "
        "over unsigned integers not implemented'), and the slab gather and "
        "gradient scatter are 1-D dynamic gathers / scatter-adds (Mosaic: "
        "'Only 2D gather is supported', no scatter-add lowering)"),
    "sparse_update": (
        "the touched-slot read is a 1-D dynamic gather from the state slab "
        "(Mosaic: 'Only 2D gather is supported')"),
}


def platform() -> str:
    """The backend dispatch decides for (tests patch this to ask the rule
    about a TPU without one attached)."""
    return jax.default_backend()


def pallas_allowed(engine: str) -> bool:
    """May dispatch route work to the Pallas ``engine`` on this platform?"""
    return not (platform() == "tpu" and engine in TPU_REFUSED)


def describe() -> list[str]:
    """One line per engine: the decision on this platform and its reason."""
    p = platform()
    out = []
    for engine in ("fused_embed", "sparse_update"):
        if pallas_allowed(engine):
            out.append(f"{engine}: Pallas eligible on {p}"
                       + (" (interpret mode)" if p != "tpu" else ""))
        else:
            out.append(f"{engine}: excluded on {p}: {TPU_REFUSED[engine]}")
    return out
