"""setup_s (s): process start to the window's start: loading, making the
weights on the chip, building the engine, compiling or loading from the
compile cache and warming the cell's shapes, and drawing the traffic."""


def read(win):
    return win.setup_s
