"""A number the run already holds under ``key`` (a program counter's
delta over the window, such as the executables compiled in it)."""


def read(run: dict, args: dict):
    return run["values"].get(args["key"])
