"""Share of the traced serving window in which no operation ran on the
chip, averaged over the chips."""


def read(ctx):
    red = ctx["reduction"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
