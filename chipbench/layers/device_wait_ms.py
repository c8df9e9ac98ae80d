"""Device: host time per query (ms) spent waiting for the ids and scores
to be ready once dispatch has returned, from `ResultSet.to_numpy`'s
`device_wait` span, over the span-traced requests sent after the
window."""


def read(run):
    if not run.n_traced or "device_wait" not in run.spans:
        return None
    return run.spans["device_wait"] / run.n_traced
