"""Plain float32 references of the served model families, one module per
family (``bench/reference/<family>.py``), found by the ``reference`` key of
a configuration file.  They import nothing of the program."""
