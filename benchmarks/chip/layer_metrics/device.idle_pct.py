"""Device idle share over the traced window (%): 100 x (1 - union of device
op intervals / window).  Reads ``device.idle_pct.<cell kind>``."""

import cb_trace


def read(ctx: dict):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    return cb_trace.idle_pct(tr, ctx["lo"], ctx["hi"])
