"""One file per driver, found by the name in the cell's file. A driver
runs the program and returns raw observations, never metrics."""
