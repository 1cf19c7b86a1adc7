"""Seconds from the process's start to the window's: device check, data
generation, store build or reopen, warm-up and compiles."""


def read(ctx):
    return ctx.setup_s
