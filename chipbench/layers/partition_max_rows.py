"""Index build: rows in the largest partition after set-up, from the
scheduler's `partition_max_rows` gauge. The build splits every
partition above the monitor's split bar, so this is at most the bar
unless a partition's rows are all equal; each probed partition is
scanned at p_max, its multiple of the tile."""


def read(run):
    v = run.before.get("scheduler", {}).get("partition_max_rows")
    return None if v is None else float(v)
