"""Result transfer: host time per query (ms) of the device->host copies
of ids and scores, from `ResultSet.to_numpy`'s `fetch` span, over the
span-traced requests sent after the window."""


def read(run):
    if not run.n_traced or "fetch" not in run.spans:
        return None
    return run.spans["fetch"] / run.n_traced
