"""setup_s: from the start of this process to the opening of the window:
spawning the server, loading and materialising parameters, compiling or
loading every program, the reference check, warm traffic or lead-in."""


def read(run):
    return run["setup_s"]
