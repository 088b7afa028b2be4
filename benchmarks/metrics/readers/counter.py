"""A program counter's change over the window."""


def read(ctx, key):
    return ctx.measured.get(key)
