"""Share of the overlaps the polisher asked the device aligner to align
that were aligned on the host instead (outside the length ladder, band
or cost rejects, chunks failed on the device), over the window's
completed jobs (the scheduler's counters,
`Polisher.occupancy_stats["aligner"]` `pairs` and `host_pairs`). None
where the program keeps no such counters."""


def read(run):
    pairs = host = 0
    for j in run.done:
        e = (j.occupancy or {}).get("aligner") or {}
        pairs += e.get("pairs", 0)
        host += e.get("host_pairs", 0)
    return 100.0 * host / pairs if pairs else None
