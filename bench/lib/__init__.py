"""The benchmark's own code: traffic, data, reference, trace reduction, and
the code that runs the system under test."""
