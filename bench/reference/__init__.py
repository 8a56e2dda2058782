"""Plain float32 references, one module per model family (named by a
configuration file's ``reference`` key)."""
