"""Index layout: live rows over the rows every probe scans, k x p_max,
after set-up (%). Each probed partition is scanned at p_max rows, so
the rest is padding."""


def read(run):
    lay = run.layout
    return 100.0 * float(lay["counts"].sum()) / (lay["k"] * lay["p_max"])
