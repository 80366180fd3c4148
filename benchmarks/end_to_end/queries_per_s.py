"""Requests answered inside the window over the window's length."""


def read(ctx):
    return ctx.answered_in_window / ctx.seconds
