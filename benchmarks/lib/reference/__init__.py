"""Plain float32 references, one file per architecture.  They import
nothing of the program and take no weights, scales or tables from it."""
