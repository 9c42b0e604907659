"""Share of the traced window in which no operation ran on the device
(mean over the cell's chips), in percent."""


def read(run):
    return None if run.reduction is None else run.reduction.idle_pct
