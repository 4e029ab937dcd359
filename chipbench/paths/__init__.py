"""Execution paths of the system under test, one file per path."""
