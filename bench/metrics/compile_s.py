"""Launcher: seconds of backend compiles and persistent-cache loads before
the window opened (the benchmark's copy of the compile clock)."""


def read(ctx):
    return ctx.compile_setup_s
