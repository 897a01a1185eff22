"""Cache manager: share of the blocks reused in the window that were
still in the device pool and mapped copy-on-write (tier 0), against
those injected from a host payload or fetched from a lower tier
(engine counters)."""


def read(ctx):
    c = ctx.counts
    cow = c["cow_share_hits"] + c["segment_share_hits"]
    other = (c["inject_hits"] + c["segment_inject_hits"]
             + c["shared_fetch_hits"])
    return 100.0 * cow / (cow + other) if cow + other else None
