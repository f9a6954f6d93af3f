"""Host milliseconds the gateway spends per launched batch: its batch
formation (``gw.form``) and launch (``gw.launch``) spans in the window,
over the launches.  The launch includes padding and the asynchronous
enqueue of the plan, not the device's work."""


def read(ctx):
    form = [s.t1 - s.t0 for s in ctx.spans if s.name == "gw.form"]
    launch = [s.t1 - s.t0 for s in ctx.spans if s.name == "gw.launch"]
    if not launch:
        return None
    return 1e3 * (sum(form) + sum(launch)) / len(launch)
