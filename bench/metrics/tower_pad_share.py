"""Rows the expensive tower's program computed in the window that held no
tokens (padding to its batch, or an all-zero empty slot), as a share of
all rows it computed; both counted at the jitted call."""


def read(ctx):
    c = ctx["towers"]["expensive"]
    return 100.0 * (c.computed - c.useful) / c.computed if c.computed else None
