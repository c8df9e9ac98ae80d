"""Executor: host time per query (ms) of the jitted `_run_spec` call,
until it returns, from the executor's `dispatch` span, over the
span-traced requests sent after the window."""


def read(run):
    if not run.n_traced or "dispatch" not in run.spans:
        return None
    return run.spans["dispatch"] / run.n_traced
