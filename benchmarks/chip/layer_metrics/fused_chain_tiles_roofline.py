"""The fused chain kernel's share of its roofline (%): the least time for
the work of the morsels it ran over its summed device time.  The work is
the window's (cb_work), times the share of morsels that ran as one fused
launch: morsels that ran another way are not the kernel's.  Reads
``fused_chain_tiles_roofline.<cell kind>``."""

import cb_trace
import cb_work

KERNEL = "fused_chain_tiles"


def read(ctx: dict):
    tr, ex = ctx.get("trace"), ctx.get("executor") or {}
    if tr is None or ctx.get("peaks") is None or not ex.get("morsels"):
        return None
    kernel_s = cb_trace.kernel_ns(tr, ctx["lo"], ctx["hi"], KERNEL) / 1e9
    if kernel_s <= 0.0:
        return None
    share = ex["fused_launches"] / ex["morsels"]
    work = ctx["work"]
    least_s, bound = cb_work.least_time(work["bytes"] * share, work["ops"] * share, ctx["peaks"])
    ctx.setdefault("notes", {})[f"{KERNEL}_roofline"] = {"least_s": least_s, "bound": bound, "kernel_s": kernel_s}
    return 100.0 * least_s / kernel_s
