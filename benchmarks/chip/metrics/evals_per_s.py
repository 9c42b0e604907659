"""Makespan evaluations (plan × noise row) completed in the window, over
the window's length."""


def read(run):
    return run.player.attempted / run.window_s
