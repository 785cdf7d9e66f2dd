"""Plain float32 references of the models the configurations train, one
file per ``data.model``: ``init`` (the benchmark's own weights from a
seed, in the program's parameter layout, any pytree), ``apply`` (the
forward pass) and ``forward_flops`` (one sample, from shapes).  A file
may also give ``loss`` and ``metrics`` (its own objective and scoring,
``bench/fedat_ref.py``; a classifier's by default) and ``place`` (its
weights and the reference's state over the cell's chips,
``bench/run.py``; one device by default)."""
