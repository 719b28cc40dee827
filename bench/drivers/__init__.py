"""Drivers: the windowed loop of each kind of cell, named by a mix's ``driver``."""
