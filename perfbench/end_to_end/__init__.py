"""One end-to-end metric per file: ``read(obs)`` on what the client saw."""
