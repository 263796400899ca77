"""The benchmark's harness.  Nothing here runs at import."""
