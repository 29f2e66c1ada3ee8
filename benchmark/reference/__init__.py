"""Plain references, one per configuration family, independent of the program."""
