"""Share of the DP cells launched that requests asked for: the sum of
``len(q) * len(r)`` over the requests answered in the traced slice, over
``rows * q_bucket * r_bucket`` of the batches harvested in it.  A batch's
bucket comes from its ``gw.form`` span, its rows from the service's
``block_for``; the two sums differ only by batches in flight at the
window's edges."""


def read(ctx):
    form, launched, cells = {}, {}, 0
    for s in ctx.spans:              # spans are in start order
        a = s.args or {}
        if s.name == "gw.form" and "bucket" in a:
            form[s.tid] = (a["channel"], tuple(a["bucket"]))
        elif s.name == "gw.launch" and s.tid in form:
            launched[(s.tid, a.get("seq"))] = form[s.tid]
        elif s.name == "gw.harvest":
            key = (s.tid, a.get("seq"))
            if key in launched:
                channel, (qb, rb) = launched[key]
                cells += ctx.block_for(channel, (qb, rb)) * qb * rb
    win = ctx.window
    useful = sum(len(q) * len(r) for (q, r), ok, t in
                 zip(win.pairs, win.ok(), win.done)
                 if ok and win.t0 <= t <= win.t1)
    if not cells or not useful:
        return None
    return 100.0 * useful / cells
