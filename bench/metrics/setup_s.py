"""Process start through the last warm-up call: imports, fabric
generation where it is set-up, compiling or loading every program the
window uses from the persistent cache."""


def read(ctx):
    return ctx.setup_s
