"""Share of the DP cells launched that requests asked for, counted where
the work lands: the sum of ``cells_useful`` (``len(q) * len(r)`` of the
jobs a batch landed) over the sum of ``cells_launched`` (rows x q-bucket
x r-bucket of its plan) of the ``gw.harvest`` spans in the traced slice.
Both numbers come from the same batches, so the slice's edges cannot
part them.  A program whose harvest spans carry no cell counts gives
nothing."""


def read(ctx):
    useful = launched = 0
    for s in ctx.spans:
        a = s.args or {}
        if s.name == "gw.harvest" and "cells_launched" in a:
            useful += a.get("cells_useful", 0)
            launched += a["cells_launched"]
    if not launched:
        return None
    return 100.0 * useful / launched
