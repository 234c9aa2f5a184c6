"""padded_row_share: bucket-padding rows over all rows the plans ran in
the window (change in ``Executable.stats()["padded_rows"]`` over images
plus padding)."""


def read(run):
    padded = run.stats1["padded_rows"] - run.stats0["padded_rows"]
    images = sum(n for _, _, n in run.calls)
    if images + padded == 0:
        return None
    return 100.0 * padded / (images + padded)
