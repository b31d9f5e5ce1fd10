"""Source rows scanned by every COOK that started in the window, over the
time from the window's start to the last such COOK's completion (rows/s)."""


def read(ctx: dict):
    recs = ctx["records"]
    ended = [r["end"] for r in recs if "end" in r]
    if not ended:
        return None
    rows = sum(ctx["table_rows"][t] for r in recs for t in r["request"]["sources"])
    return rows / (max(ended) - ctx["window_start"])
