"""Index build: rows the build's split pass fed to 2-means, summed over
every split (a row split twice counts twice), from the scheduler's
`build_rows_split` counter after set-up: the split pass's share of
`build()`."""


def read(run):
    v = run.before.get("scheduler", {}).get("build_rows_split")
    return None if v is None else float(v)
