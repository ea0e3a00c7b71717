"""Correct answers completed inside the window, per second of window."""


def read(run):
    n = sum(1 for r in run.window if r.correct and r.done <= run.seconds)
    return n / run.seconds
