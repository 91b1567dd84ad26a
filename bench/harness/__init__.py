"""The benchmark's harness: data, reference, checks, trace reduction."""
