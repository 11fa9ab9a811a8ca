"""Mean CG iterations per solve, from the solver's ``info["iterations"]``."""


def read(ctx):
    its = ctx.counters.get("iterations")
    return None if not its else sum(its) / len(its)
