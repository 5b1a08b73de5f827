"""One module per kind of cell ("kind" in the traffic file): run(ctx) drives
the system under test through its normal entry points and returns the facts
of the run. The only code of the benchmark that imports flexflow_tpu."""
