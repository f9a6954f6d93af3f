"""Share of the roofline that the plan programs (fill and traceback)
reach: the least time the chip needs for the work the requests asked
for (``bench/work.py``: useful cells times the kernel's operations per
cell against the 32-bit vector-op peak, or the bytes against HBM
bandwidth, whichever is larger), over the device time of the programs
that finished in the window.  The work is that of the requests answered
in the window."""
from bench import work


def read(ctx):
    if ctx.trace is None or ctx.trace["ended_module_s"] <= 0:
        return None
    win = ctx.window
    lengths = [(len(q), len(r)) for (q, r), ok, t in
               zip(win.pairs, win.ok(), win.done)
               if ok and win.t0 <= t <= win.t1]
    if not lengths:
        return None
    r = work.roofline(ctx.kernel, lengths, ctx.trace["ended_module_s"],
                      ctx.device_kind)
    return {"value": 100.0 * r["share"], "bound": r["bound"]}
