"""Per-layer metric readers, one module each: read(run, name, **args) returns
the value or None when there is nothing to read (the harness then leaves the
metric out of the line). `run` has .facts (what the cell's run recorded),
.trace and .window (the reduced device trace, or None), .cell, .peaks."""
