"""Reader kinds: each module has `read(ctx, params)` and returns a number, or
None where it finds nothing to read (the harness then leaves the metric out
of the line). A per-layer metric is a file `metrics/<name>.json` naming one
of these with its parameters; a quantity none of them can read is a new file
here, never an edit."""
