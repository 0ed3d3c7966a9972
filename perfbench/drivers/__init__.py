"""One file per driver kind; a traffic mix names its driver under ``kind``."""
