"""The benchmark's yardstick: traffic and graph generation, the plain
reference, the trace reduction, the table of peaks and the algorithmic
work counts.  Nothing here imports the program under test."""
