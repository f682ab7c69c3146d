"""The benchmark's yardstick: graph and feature generation, the reference
sampler and model, trace reduction, operation and byte counts, peaks."""
