"""Set-up seconds: process start to the window's start (inputs, plans,
warm-up and any compilation), on the host clock."""


def read(run):
    return run.setup_s
