"""Host time of the grouped fold per grouped morsel (ms): 1000 x
``agg_host_s / agg_morsels`` of the pallas backend, its seconds spent
mapping grouped morsels' keys to group ids and merging partial states,
over the grouped morsels it folded in the process (warm-up included).
A program without these counters reports nothing.  Reads
``aggregate.host_ms_per_morsel.<cell kind>``."""


def read(ctx: dict):
    from repro.core.backend import get_backend

    bk = get_backend("pallas")
    morsels = getattr(bk, "agg_morsels", None)
    host_s = getattr(bk, "agg_host_s", None)
    if not morsels or host_s is None:
        return None
    return 1000.0 * host_s / morsels
