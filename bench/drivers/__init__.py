"""One driver per traffic kind: it builds the cell's inputs, hands them to
the program, times the window and decides ``correct``."""
