"""Group programs compiled inside the window: the increase of the
program's ``program_cache.compiles`` counter (should read 0)."""


def read(ctx):
    return ctx["window_compiles"]
