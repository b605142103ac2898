"""The benchmark harness: loading, seeded inputs, the reference, the
comparison and the trace reduction."""
