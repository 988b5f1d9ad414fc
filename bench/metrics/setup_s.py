"""Set-up seconds: process start until the window opens (imports, device
init, weights, compiles or cache loads, warm traffic)."""


def read(ctx):
    return ctx.setup_s
