"""One reader per metric: ``read(run)`` returns a number, or None where the
run holds nothing to read."""
