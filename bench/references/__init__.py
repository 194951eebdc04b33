"""Plain references; none imports the program."""
