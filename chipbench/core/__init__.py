"""The harness: device, traffic, weights, trace, one run."""
