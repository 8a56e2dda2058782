"""Block families, one module per family (named by a configuration file's
``reference`` key): how the family's configuration keys map onto the program
and the reference, and the operations and bytes its work needs."""
