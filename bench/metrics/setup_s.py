"""setup_s: process start to the first timed request (host clock): imports,
weights made from the seed, compiling or loading every program the
window uses, and warming them."""


def read(name, ctx):
    return ctx.setup_s
