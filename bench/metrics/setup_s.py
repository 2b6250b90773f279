"""Set-up: from the start of the process to the end of the warm-up
(imports, reaching the chip, weights, compiles or cache reads, warm-up)."""


def read(ctx):
    return ctx.setup_s
