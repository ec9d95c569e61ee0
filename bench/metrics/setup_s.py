"""Set-up time: process start to the start of the measured window (host
clock).  It holds loading, weight making, compiling or reading the compile
cache, and the first steps that the correctness check reads."""


def read(ctx):
    return ctx.setup_s
