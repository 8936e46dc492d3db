"""Seconds from the process's start to the window's: imports, the device,
weights or fields drawn on the chip, compiling (or loading from the
persistent cache) and warming up every shape the window uses."""


def read(w):
    return w.setup_s
