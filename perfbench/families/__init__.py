"""One file per model family; a configuration names its family."""
