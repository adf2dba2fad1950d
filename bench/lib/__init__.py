"""The harness: generator, reference, trace reduction, peaks."""
