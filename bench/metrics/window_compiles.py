"""Executables built inside the window, counted from JAX's own lowering
events (each compile, or load from the compilation cache, lowers first).
It should read 0."""


def read(run):
    return {"value": run.window_compiles}
