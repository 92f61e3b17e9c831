"""The benchmark's own code: cell specs, data generators, the run loop,
the trace reduction and the work counts behind the roofline shares."""
