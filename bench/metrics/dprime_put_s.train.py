"""Embedding layer, set-up: the program's ``dprime.put`` span (the D'
store's copy to the device in ``repro.core.signatures.densify_store``, up
to the arrays being ready), seconds over the run, from the program's span
totals (``repro.obs.totals``).  A program without those spans reads
nothing."""


def read(ctx):
    if not ctx:                     # no run to read the set-up of
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    t = obs.totals().get("dprime.put")
    return t["s"] if t else None
