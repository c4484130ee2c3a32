"""Seconds from the start of the process to the start of the window:
loading, making the inputs, compiling or reading the compile cache, and
the first rounds."""
UNIT = "s"


def read(win):
    return win.setup_s
