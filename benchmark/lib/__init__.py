"""What every cell shares and no later PR may edit: peaks, counts, traffic,
seeded weights, the plain reference, the trace reduction."""
