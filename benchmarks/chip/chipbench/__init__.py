"""The chip benchmark's own code: data, reference, FLOP counter, trace reduction."""
