"""SQLite store: time of the paged rerank per query (ms) -- the fetch of
the candidates' float32 rows from SQLite and their rescore -- from the
engine's `rerank` span, which the paged path alone times, over the
span-traced requests sent after the window."""


def read(run):
    if not run.layout["paged"] or not run.n_traced \
            or "rerank" not in run.spans:
        return None
    return run.spans["rerank"] / run.n_traced
