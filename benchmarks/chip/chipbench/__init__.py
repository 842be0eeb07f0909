"""The chip benchmark's own code: harness, reference, FLOP counter, trace reduction."""
