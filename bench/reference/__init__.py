"""Plain references: no import of the program under test."""
