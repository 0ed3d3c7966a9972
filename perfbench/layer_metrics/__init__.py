"""One reader per file: ``read(obs)`` returns the value, or None where there
is nothing to read (the harness then leaves the metric out of the line)."""
