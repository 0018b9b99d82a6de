"""Seconds from the start of the process to the end of the warm-up product:
building the operators, handing them to the program, symbolic work,
planning, compiling or loading from the cache, and warming up."""


def read(run):
    return {"value": run.setup_s}
