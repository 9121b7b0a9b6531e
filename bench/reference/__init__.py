"""Plain float32 references the benchmark's ``correct`` is decided by.

They import nothing of the program under test and take nothing it made:
weights and inputs come from the benchmark's own generators.
"""
