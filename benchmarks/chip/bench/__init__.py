"""The chip benchmark's yardstick: traffic generators, seeded weights, the
plain reference, FLOP and byte counts, the peaks table, the trace
reduction and the drivers that run one cell.  Nothing here is imported
by the program under test."""
