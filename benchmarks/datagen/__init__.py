"""Seeded input data, written in the file formats the apps load."""
