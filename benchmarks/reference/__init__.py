"""Plain float32 references.  Nothing here imports the program."""
