"""Useful over dispatched DP cells of the session POA engine, over the
window's completed jobs (the scheduler's occupancy counters,
`Polisher.occupancy_stats["session"]`)."""


def read(run):
    useful = total = 0
    for j in run.done:
        e = j.occupancy.get("session", {})
        useful += e.get("useful_cells", 0)
        total += e.get("total_cells", 0)
    return 100.0 * useful / total if total else None
