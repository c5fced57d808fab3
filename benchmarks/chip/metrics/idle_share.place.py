"""Percent of the traced placement window in which no operation ran on
the device."""


def read(ctx):
    share = ctx["trace"].idle_share
    return None if share is None else 100.0 * share
