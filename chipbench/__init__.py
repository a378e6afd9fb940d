"""The chip benchmark of the AsyncFLEO simulator (see README.md)."""
