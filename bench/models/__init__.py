"""Plain float32 references of the models the configurations train, one
file per ``data.model``: ``init`` (the benchmark's own weights from a
seed, in the program's parameter layout), ``apply`` (the forward pass)
and ``forward_flops`` (one sample, from shapes)."""
