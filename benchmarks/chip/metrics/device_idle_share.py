"""Percent of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window,
averaged over the chips used."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
