"""Plain references, one per family. They import nothing of the program."""
