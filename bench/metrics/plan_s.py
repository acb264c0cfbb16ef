"""plan_s: host seconds to build, plan and lower the cell's network
(the program's planner: ``core/pathfinder``, ``core/slicing``,
``optimize/``), measured around the entry that does it."""


def read(ctx):
    return ctx["plan_s"]
