"""Share of morsels that ran as one fused launch (%): 100 x fused launches /
morsels, ExecutorStats over the window.  Reads ``executor.fused_pct.<cell kind>``."""


def read(ctx: dict):
    ex = ctx.get("executor") or {}
    if not ex.get("morsels"):
        return None
    return 100.0 * ex["fused_launches"] / ex["morsels"]
