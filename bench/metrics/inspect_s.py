"""Seconds of the plan build in set-up (the first call's plan miss), from
the program's ``RunStats``."""


def read(ctx):
    return ctx.setup.get("inspect_s") or None
