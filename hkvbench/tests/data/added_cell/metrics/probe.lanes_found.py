"""Lanes the probe entry found in the window, from its `counters` hook."""


def read(ctx):
    found = ctx.counters.get("lanes_found")
    return None if found is None else float(found)
