"""Index build: host time of the build's split pass (ms), the
`build_split` stage, from the scheduler's `build_split_ms` gauge after
set-up: the pass's share of `build()`."""


def read(run):
    v = run.before.get("scheduler", {}).get("build_split_ms")
    return None if v is None else float(v)
