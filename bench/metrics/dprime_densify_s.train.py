"""Embedding layer, set-up: the program's ``dprime.densify`` span (the
host fill of the D' store in ``repro.core.signatures.densify_store``),
seconds over the run, from the program's span totals
(``repro.obs.totals``).  A program without those spans reads nothing."""


def read(ctx):
    if not ctx:                     # no run to read the set-up of
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    t = obs.totals().get("dprime.densify")
    return t["s"] if t else None
