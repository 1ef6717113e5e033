"""Plain references the correctness checks compare with; they import
nothing of the system under test."""
