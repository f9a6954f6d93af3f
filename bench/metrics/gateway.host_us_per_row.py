"""Host microseconds the gateway spends per row it lands: its batch
formation (``gw.form``), launch (``gw.launch``, the padding inside it)
and landing (``gw.land``) spans in the traced slice, over the rows the
``gw.land`` spans landed (their ``n``).  One million over it is the
host's ceiling in requests per second.  Beside it, ``pad_us`` and
``land_us``: the ``gw.pad`` and ``gw.land`` time per row.  A program
whose harvest has no ``gw.land`` span gives nothing."""

SPANS = ("gw.form", "gw.launch", "gw.pad", "gw.land")


def read(ctx):
    total = dict.fromkeys(SPANS, 0.0)
    rows = 0
    for s in ctx.spans:
        if s.name in total:
            total[s.name] += s.t1 - s.t0
            if s.name == "gw.land":
                rows += (s.args or {}).get("n", 0)
    if not rows:
        return None
    per_row_us = 1e6 / rows
    host = total["gw.form"] + total["gw.launch"] + total["gw.land"]
    return {"value": per_row_us * host,
            "pad_us": per_row_us * total["gw.pad"],
            "land_us": per_row_us * total["gw.land"]}
